"""Multi-head Latent Attention (DeepSeek-V3).

Train/prefill path materializes per-head k/v from the compressed latent;
the decode path uses the *absorbed-weights* formulation so the KV cache
holds only (kv_lora_rank + qk_rope_head_dim) floats per token:

  q_lat  = q_nope @ W_UK            (query moved into latent space)
  score  = q_lat . c_kv + q_rope . k_rope
  ctx    = softmax(score) @ c_kv    (context in latent space)
  out    = (ctx @ W_UV) @ W_O

This is DeepSeek's decode trick: the cache is 576 floats/token instead of
H * (192 + 128) = 40960, which is what makes 32k/128-batch decode feasible.

Cache layout: one (B, C, kv_lora_rank + qk_rope_head_dim) tensor holding
``[latent | rope key]`` concatenated per token.  The concatenated row is
exactly the decode key (``[q_lat | q_rope] . [c_kv | k_rope]`` is the
score), its ``kv_lora_rank`` prefix is exactly the decode value, and one
``cache_update`` scatter per step replaces the two the split layout
needed.  The flash decode path (``impl="flash"``) feeds the kernel the
cache as both K and V with ``v_width=kv_lora_rank`` — zero reshuffling,
and KV blocks beyond each row's prefix are never read.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import quant
from repro.kernels.constants import NEG_INF
from repro.models import layers
from repro.models.attention import attention
from repro.sharding.specs import annotate, shard


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


def softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale**2`` when the
    config scales its rotary positions (DeepSeek-V3: ~1.874)."""
    m = cfg.mla
    return layers.yarn_softmax_factor(cfg.rope_scaling) / math.sqrt(
        m.qk_nope_head_dim + m.qk_rope_head_dim)


# -- params -------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    di = layers.dense_init
    return {
        "wq_a": annotate(di(ks[0], (d, m.q_lora_rank)), "d_model", "q_rank"),
        "q_norm": annotate(jnp.ones((m.q_lora_rank,), jnp.float32), "q_rank"),
        "wq_b": annotate(di(ks[1], (m.q_lora_rank, h, qk_hd)),
                         "q_rank", "heads", "head_dim"),
        # kv down-projection also produces the shared rope key
        "wkv_a": annotate(di(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
                          "d_model", "kv_rank"),
        "kv_norm": annotate(jnp.ones((m.kv_lora_rank,), jnp.float32),
                            "kv_rank"),
        "wk_b": annotate(di(ks[3], (m.kv_lora_rank, h, m.qk_nope_head_dim)),
                         "kv_rank", "heads", "head_dim"),
        "wv_b": annotate(di(ks[4], (m.kv_lora_rank, h, m.v_head_dim)),
                         "kv_rank", "heads", "head_dim"),
        "wo": annotate(di(ks[5], (h, m.v_head_dim, d), in_axis=(0, 1)),
                       "heads", "head_dim", "d_model"),
    }


def _project_q(cfg: ModelConfig, p, x, positions):
    """(B,S,d) -> q_nope (B,S,H,nope), q_rope (B,S,H,rope) (rope applied)."""
    m = cfg.mla
    dt = x.dtype
    ql = _rms(x @ p["wq_a"].astype(dt), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"].astype(dt))
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = q[..., m.qk_nope_head_dim:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta,
                               yarn=cfg.rope_scaling)
    return q_nope, q_rope


def _project_kv_latent(cfg: ModelConfig, p, x, positions):
    """(B,S,d) -> normed latent (B,S,r), roped shared key (B,S,rope)."""
    m = cfg.mla
    dt = x.dtype
    kv = x @ p["wkv_a"].astype(dt)
    latent = _rms(kv[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., m.kv_lora_rank:]
    k_rope = layers.apply_rope(k_rope[:, :, None, :], positions,
                               cfg.rope_theta,
                               yarn=cfg.rope_scaling)[:, :, 0, :]
    return latent, k_rope


# -- train / prefill -----------------------------------------------------------

def mla_self_attention(cfg: ModelConfig, p, x, positions, *,
                       impl: str = "dense", chunk: int = 1024):
    """Full-sequence causal MLA. Returns (out, (latent, k_rope)) so the
    serve path can build the latent cache from prefill."""
    m = cfg.mla
    dt = x.dtype
    b, s, _ = x.shape
    q_nope, q_rope = _project_q(cfg, p, x, positions)
    latent, k_rope = _project_kv_latent(cfg, p, x, positions)

    k_nope = jnp.einsum("bsr,rhk->bshk", latent, p["wk_b"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", latent, p["wv_b"].astype(dt))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "kv_seq", "heads", "head_dim")
    v = shard(v, "batch", "kv_seq", "heads", "head_dim")

    o = attention(cfg, q, k, v, q_pos=positions, kv_pos=positions,
                  causal=True, impl=impl, chunk=chunk,
                  scale=softmax_scale(cfg),
                  unroll=cfg.unroll_time_chunks,
                  causal_kv_trim=cfg.causal_kv_trim)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return shard(out, "batch", "seq", "d_model"), (latent, k_rope)


# -- decode (absorbed weights, latent cache) -------------------------------------

def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """``cfg.kv_quant`` quantizes the concatenated ``[latent | rope]``
    row ONCE — it is both the decode key and (prefix-sliced) value, so
    one (B, C) float32 ``kv_scale`` leaf serves as ``k_scale`` and
    ``v_scale`` alike (per-row scaling commutes with the ``v_width``
    prefix slice)."""
    m = cfg.mla
    width = m.kv_lora_rank + m.qk_rope_head_dim
    if cfg.kv_quant is not None:
        qdt = quant.quant_dtype(cfg.kv_quant)
        return {"kv": jnp.zeros((batch, max_len, width), qdt),
                "kv_scale": jnp.zeros((batch, max_len), jnp.float32)}
    return {"kv": jnp.zeros((batch, max_len, width), dtype)}


def mla_cache_axes(cfg: ModelConfig = None) -> Dict[str, Tuple]:
    ax = {"kv": ("batch", "kv_seq", "kv_rank")}
    if cfg is not None and cfg.kv_quant is not None:
        ax["kv_scale"] = ("batch", "kv_seq")
    return ax


def prefill_mla_cache(cfg: ModelConfig, latent, k_rope, max_len: int,
                      dtype=jnp.bfloat16):
    cache = init_mla_cache(cfg, latent.shape[0], max_len, dtype)
    kv = jnp.concatenate([latent, k_rope], axis=-1)
    if cfg.kv_quant is not None:
        kv, sc = quant.quantize(kv, cfg.kv_quant)
        cache["kv_scale"] = jax.lax.dynamic_update_slice(
            cache["kv_scale"], sc, (0, 0))
    else:
        kv = kv.astype(dtype)
    cache["kv"] = jax.lax.dynamic_update_slice(cache["kv"], kv, (0, 0, 0))
    return cache


def mla_prefill_chunk(cfg: ModelConfig, p, x, cache, offset, valid_len):
    """One chunk of chunked prefill through one MLA layer (absorbed).

    x: (B, T, d) at absolute positions ``offset + i``; cache: the
    latent ``{"kv"}`` tensor holding positions ``< offset``.  The
    absorbed-weights trick extends from decode verbatim: the query
    moves into latent space (``q_lat = q_nope @ W_UK``), the
    concatenated ``[latent | rope]`` row *is* the key — both for the
    cache prefix and for the chunk's own (not yet written) rows — and
    its latent prefix is the value (``v_width``), so the chunk attends
    through ``kernels/prefill_attention`` with zero reshuffling and no
    per-head K/V materialisation.  Tokens ``>= valid_len`` (final
    partial chunk's right-padding) land on never-valid slots.
    Returns (out (B, T, d), new_cache).
    """
    from repro.kernels.prefill_attention import ops as pf_ops
    from repro.models.attention import chunk_kv_write
    m = cfg.mla
    dt = x.dtype
    b, t = x.shape[:2]
    off = jnp.asarray(offset, jnp.int32)
    positions = (off[:, None] if off.ndim else off) \
        + jnp.arange(t, dtype=jnp.int32)[None]
    positions = jnp.broadcast_to(positions, (b, t))

    q_nope, q_rope = _project_q(cfg, p, x, positions)          # (B,T,H,*)
    latent_new, k_rope_new = _project_kv_latent(cfg, p, x, positions)
    kv_new = jnp.concatenate([latent_new, k_rope_new], axis=-1)  # (B,T,r+rr)

    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"].astype(dt))
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)          # (B,T,H,r+rr)
    mode = cfg.kv_quant
    kv_sc = cache["kv_scale"][:, :, None] if mode is not None else None
    kvx = kv_new[:, :, None, :]                                # (B,T,1,r+rr)
    kvc = cache["kv"][:, :, None, :]                           # (B,C,1,r+rr)
    ctx = pf_ops.prefill_attention(
        q_eff, kvx, kvx, kvc, kvc, off, scale=softmax_scale(cfg),
        v_width=m.kv_lora_rank, k_scale=kv_sc).astype(dt)      # (B,T,H,r)

    if mode is not None:
        kv_new, sc_new = quant.quantize(kv_new, mode)
        sc = chunk_kv_write(cache["kv_scale"], sc_new, off, valid_len)
        sc = shard(sc, "batch", "kv_seq")
    kv = chunk_kv_write(cache["kv"], kv_new, off, valid_len)
    kv = shard(kv, "batch", "kv_seq", "kv_rank")
    o = jnp.einsum("bqhr,rhk->bqhk", ctx, p["wv_b"].astype(dt))
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))
    out = shard(out, "batch", "seq", "d_model")
    return out, ({"kv": kv, "kv_scale": sc} if mode is not None
                 else {"kv": kv})


# -- paged (block pools + page-table indirection) ------------------------------

def paged_row_width(cfg: ModelConfig) -> int:
    """Lanes of a paged pool row: ``[latent | rope key]`` zero-padded to
    a multiple of 128.  On a TPU an array whose last dim is not a
    multiple of 128 takes a default layout that is not row-major (576
    wide: pages innermost), which the kernels cannot read in place; a
    padded row keeps the pool row-major, at the bytes its tiles would
    take anyway."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // 128) * 128


def _pad_row(x, width: int):
    """Zero-pad the last dim of ``x`` to ``width`` (the added lanes
    score exactly 0 against a zero-padded query)."""
    pad = width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def init_paged_mla_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Physical page pool for one MLA layer: (P, page_size, width)
    ``[latent | rope key | 0]`` rows (``paged_row_width``).  Same
    one-page-id-per-position space as ``attention.init_paged_kv_pools``
    (page 0 = scratch)."""
    width = paged_row_width(cfg)
    if cfg.kv_quant is not None:
        qdt = quant.quant_dtype(cfg.kv_quant)
        return {"kv": jnp.zeros((num_pages, page_size, width), qdt),
                "kv_scale": jnp.zeros((num_pages, page_size), jnp.float32)}
    return {"kv": jnp.zeros((num_pages, page_size, width), dtype)}


def mla_paged_decode_attention(cfg: ModelConfig, p, x, cache, layer,
                               cur_len, page_table, cache_impl: str = "auto"):
    """One-token absorbed-MLA decode against a *paged* latent cache.

    x: (B, 1, d); cache: {"kv"} (L, P, page_size, W) pools of a layer
    stack (``paged_row_width``), this layer at index ``layer``; cur_len: (B,);
    page_table: (B, NB) int32 (masked rows touch only the scratch
    page).  The absorbed trick carries over unchanged: the pool row is
    both key and (``v_width``-truncated) value, and the kernels take
    the pool as one kv head without a head axis, in place.
    """
    from repro.kernels.cache_update import ops as cu_ops
    from repro.kernels.decode_attention import ops as da_ops
    m = cfg.mla
    dt = x.dtype
    b = x.shape[0]
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    positions = cur[:, None]

    q_nope, q_rope = _project_q(cfg, p, x, positions)          # (B,1,H,*)
    latent_new, k_rope_new = _project_kv_latent(cfg, p, x, positions)
    width = paged_row_width(cfg)
    kv_new = _pad_row(jnp.concatenate([latent_new, k_rope_new], axis=-1),
                      width)                                   # (B,1,W)

    mode = cfg.kv_quant
    sc = None
    ones = jnp.ones((b,), jnp.int32)
    if mode is not None:
        kv, sc = cu_ops.quant_paged_cache_update(
            cache["kv"], cache["kv_scale"], layer, kv_new, page_table, cur,
            ones, mode, impl=cache_impl)
    else:
        kv = cu_ops.paged_cache_update(cache["kv"], layer, kv_new,
                                       page_table, cur, ones,
                                       impl=cache_impl)

    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"].astype(dt))
    q_eff = _pad_row(jnp.concatenate([q_lat, q_rope], axis=-1),
                     width)                                    # (B,1,H,W)
    # The (L, P, ps, W) pool is read as it is stored: one kv head with
    # no head axis (a (..., 1, W) view would relayout the pool).
    ctx = da_ops.decode_attention_paged(
        q_eff, kv, kv, layer, page_table, cur,
        scale=softmax_scale(cfg), v_width=m.kv_lora_rank,
        k_scale=sc[..., None] if mode is not None else None
    ).astype(dt)                                               # (B,1,H,r)

    o = jnp.einsum("bqhr,rhk->bqhk", ctx, p["wv_b"].astype(dt))
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))
    out = shard(out, "batch", "seq", "d_model")
    return out, ({"kv": kv, "kv_scale": sc} if mode is not None
                 else {"kv": kv})


def mla_paged_prefill_chunk(cfg: ModelConfig, p, x, cache, layer, offset,
                            valid_len, page_table, cache_impl: str = "auto"):
    """One chunk of chunked prefill through one MLA layer, paged.

    Mirrors ``mla_prefill_chunk`` with the pool view in place of the
    per-slot cache: layer ``layer`` of the stacked (L, P, page_size, W)
    pool; offset/valid_len are (B,) (rows with
    ``valid_len == 0`` are masked to the scratch page and discarded by
    the caller).  Attend first — the chunk's own rows arrive as
    separate operands — then scatter.
    """
    from repro.kernels.cache_update import ops as cu_ops
    from repro.kernels.prefill_attention import ops as pf_ops
    m = cfg.mla
    dt = x.dtype
    b, t = x.shape[:2]
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    positions = off[:, None] + jnp.arange(t, dtype=jnp.int32)[None]

    q_nope, q_rope = _project_q(cfg, p, x, positions)          # (B,T,H,*)
    latent_new, k_rope_new = _project_kv_latent(cfg, p, x, positions)
    width = paged_row_width(cfg)
    kv_new = _pad_row(jnp.concatenate([latent_new, k_rope_new], axis=-1),
                      width)                                   # (B,T,W)

    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"].astype(dt))
    q_eff = _pad_row(jnp.concatenate([q_lat, q_rope], axis=-1),
                     width)                                    # (B,T,H,W)
    mode = cfg.kv_quant
    kvx = kv_new[:, :, None, :]                                # (B,T,1,W)
    kvp = cache["kv"]                          # (L,P,ps,W): no head axis
    ctx = pf_ops.prefill_attention_paged(
        q_eff, kvx, kvx, kvp, kvp, layer, page_table, off,
        scale=softmax_scale(cfg), v_width=m.kv_lora_rank,
        k_scale=(cache["kv_scale"][..., None] if mode is not None
                 else None)).astype(dt)                        # (B,T,H,r)

    valids = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    if mode is not None:
        kv, sc = cu_ops.quant_paged_cache_update(
            cache["kv"], cache["kv_scale"], layer, kv_new, page_table, off,
            valids, mode, impl=cache_impl)
    else:
        kv = cu_ops.paged_cache_update(cache["kv"], layer, kv_new,
                                       page_table, off, valids,
                                       impl=cache_impl)
    o = jnp.einsum("bqhr,rhk->bqhk", ctx, p["wv_b"].astype(dt))
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))
    out = shard(out, "batch", "seq", "d_model")
    return out, ({"kv": kv, "kv_scale": sc} if mode is not None
                 else {"kv": kv})


def mla_decode_attention(cfg: ModelConfig, p, x, cache, cur_len,
                         cache_impl: str = "auto", impl: str = "dense"):
    """One-token absorbed-MLA decode. x: (B,1,d).

    ``cur_len`` is a scalar (synchronized decode) or a (B,) vector of
    per-slot positions (continuous batching); the vector path scatters
    each row's ``[latent | rope]`` row at its own offset via one
    ``kernels/cache_update`` call.

    impl: "dense" materialises the (B, H, 1, C) score tensor over the
    whole cache; "flash" runs ``kernels/decode_attention`` with the
    concatenated cache as both K and V (``v_width`` keeps the value
    read to the latent prefix) — blocks beyond each row's prefix are
    never read.
    """
    m = cfg.mla
    dt = x.dtype
    b = x.shape[0]
    cur = jnp.asarray(cur_len, jnp.int32)
    per_row = cur.ndim == 1
    positions = cur[:, None] if per_row else jnp.full((b, 1), cur, jnp.int32)

    q_nope, q_rope = _project_q(cfg, p, x, positions)          # (B,1,H,*)
    latent_new, k_rope_new = _project_kv_latent(cfg, p, x, positions)
    kv_new = jnp.concatenate([latent_new, k_rope_new], axis=-1)  # (B,1,r+rr)

    mode = cfg.kv_quant
    sc = None
    if per_row:
        from repro.kernels.cache_update import ops as cu_ops
        slot_rows = jnp.minimum(cur, cache["kv"].shape[1] - 1)
        if mode is not None:
            kv, sc = cu_ops.quant_cache_update(
                cache["kv"], cache["kv_scale"], kv_new, slot_rows, mode,
                impl=cache_impl)
        else:
            kv = cu_ops.cache_update(cache["kv"], kv_new, slot_rows,
                                     impl=cache_impl)
    elif mode is not None:
        kv_codes, sc_new = quant.quantize(kv_new, mode)
        kv = jax.lax.dynamic_update_slice(cache["kv"], kv_codes,
                                          (0, cur_len, 0))
        sc = jax.lax.dynamic_update_slice(cache["kv_scale"], sc_new,
                                          (0, cur_len))
    else:
        kv = jax.lax.dynamic_update_slice(
            cache["kv"], kv_new.astype(cache["kv"].dtype), (0, cur_len, 0))
    kv = shard(kv, "batch", "kv_seq", "kv_rank")
    if mode is not None:
        sc = shard(sc, "batch", "kv_seq")
    new_cache = {"kv": kv, "kv_scale": sc} if mode is not None \
        else {"kv": kv}

    # absorb W_UK into the query: (B,1,H,nope) @ (r,H,nope) -> (B,1,H,r)
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, p["wk_b"].astype(dt))

    scale = softmax_scale(cfg)
    if impl == "flash":
        from repro.kernels.decode_attention import ops as da_ops
        # [q_lat | q_rope] . [latent | rope] is the absorbed score, so
        # the concatenated cache row *is* the key; its latent prefix is
        # the value (KVH=1, G=H — every query head shares the latent).
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)   # (B,1,H,r+rr)
        kv4 = kv[:, :, None, :]                             # (B,C,1,r+rr)
        ctx = da_ops.decode_attention(
            q_eff, kv4, kv4, cur, scale=scale, v_width=m.kv_lora_rank,
            k_scale=sc[:, :, None] if mode is not None else None
        ).astype(dt)                                        # (B,1,H,r)
    elif impl == "dense":
        kv_f = quant.dequantize(kv, sc) if mode is not None else kv
        latent = kv_f[..., :m.kv_lora_rank]
        k_rope = kv_f[..., m.kv_lora_rank:]
        s_lat = jnp.einsum("bqhr,bsr->bhqs", q_lat, latent.astype(dt))
        s_rope = jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope.astype(dt))
        scores = (s_lat + s_rope).astype(jnp.float32) * scale

        cache_len = kv.shape[1]
        # per-slot validity against each row's own position counter; the
        # row dim is degenerate (1,1,1,C) when cur is a scalar.
        cur_col = cur[:, None] if per_row else cur[None, None]
        valid = jnp.arange(cache_len)[None, None, None, :] \
            <= cur_col[:, None, None, :]         # (B|1,1,1,C) over (B,H,1,C)
        scores = jnp.where(valid, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqs,bsr->bqhr", probs.astype(dt),
                         latent.astype(dt))
    else:
        raise ValueError(f"unknown decode attention impl {impl!r}")

    o = jnp.einsum("bqhr,rhk->bqhk", ctx, p["wv_b"].astype(dt))
    out = jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))
    out = shard(out, "batch", "seq", "d_model")
    return out, new_cache
