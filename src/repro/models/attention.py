"""Attention: GQA with the assigned archs' variants, train/prefill/decode.

Covers:
  * grouped-query attention (all archs; MHA is the kv_heads==heads case),
  * qk-norm on per-head q/k (qwen3),
  * attention-logit soft-capping (gemma2),
  * sliding-window masking for local layers (gemma2),
  * RoPE / M-RoPE positions (applied here, built in layers.py),
  * cross-attention (whisper decoder),
  * a KV-cache decode path (one new token against a cache of seq_len).

Implementations (full-sequence ``attention``):
  * ``dense``   — materialises (B, H, Sq, Skv) scores; right for short seqs
                  and the smoke tests.
  * ``chunked`` — lax.scan over query blocks; bounds the live score tensor
                  to (B, H, chunk, Skv). This is the XLA path the dry-run
                  lowers for 32k prefill (flash-style memory behaviour
                  without a custom kernel).
  * ``pallas``  — the flash-attention Pallas kernel (kernels/flash_attention),
                  TPU-targeted, validated in interpret mode.  Self-attention
                  with contiguous-from-zero positions only: it derives the
                  causal/window mask from block indices, so calls carrying a
                  ``kv_valid`` mask or ``causal=False`` (cross-attention)
                  raise instead of silently dropping those constraints.

The choice is per-call (``impl=``); models pick dense for tiny smoke
configs and chunked for production shapes (see model.py).

Decode (``decode_self_attention``) has its own impl pair, selected by
``cfg.decode_attn_impl`` (resolved in blocks.py):
  * ``dense``   — masked attend over the whole (B, C) cache with an
                  explicit slot->position timeline (row-degenerate (1, C)
                  when every row is at the same position).
  * ``flash``   — the flash-decode kernel family
                  (kernels/decode_attention): online-softmax sweep over KV
                  blocks, per-row ``cur_len`` via scalar prefetch so cache
                  blocks beyond a row's valid prefix are never read from
                  HBM; ring-buffer slot arithmetic, GQA head packing, and
                  soft-capping happen in-kernel, so no (B, C)
                  position/validity tensors are built per decode step.
                  Dispatches to Pallas on TPU and a length-aware masked
                  lax sweep elsewhere.  Decode is memory-bound, so the
                  skipped HBM bytes are the J/token lever (see
                  benchmarks/bench_decode.py).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import quant
from repro.kernels.constants import NEG_INF
from repro.models import layers
from repro.sharding.specs import annotate, shard


# -- params -------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    """GQA projection weights.

    q: (d, H, hd)   k,v: (d, KVH, hd)   o: (H, hd, d)
    qk-norm adds per-head-dim scales (qwen3 style, applied on the head dim).
    """
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": annotate(
            layers.dense_init(k1, (d, h, hd)), "d_model", "heads", "head_dim"),
        "wk": annotate(
            layers.dense_init(k2, (d, kvh, hd)), "d_model", "kv_heads",
            "head_dim"),
        "wv": annotate(
            layers.dense_init(k3, (d, kvh, hd)), "d_model", "kv_heads",
            "head_dim"),
        "wo": annotate(
            layers.dense_init(k4, (h, hd, d), in_axis=(0, 1)),
            "heads", "head_dim", "d_model"),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = annotate(jnp.ones((hd,), jnp.float32), "head_dim")
        p["k_norm"] = annotate(jnp.ones((hd,), jnp.float32), "head_dim")
    return p


def _rms_head(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# -- qkv projection -----------------------------------------------------------

def project_qkv(cfg: ModelConfig, p, x, positions,
                kv_x: Optional[jnp.ndarray] = None,
                rope: bool = True):
    """Project hidden states to (q, k, v) with RoPE applied.

    kv_x: source of k/v for cross-attention (defaults to x).
    Returns q (B,Sq,H,hd), k,v (B,Skv,KVH,hd).
    """
    dt = x.dtype
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(dt))
    if cfg.qk_norm and "q_norm" in p:
        q = _rms_head(q, p["q_norm"])
        k = _rms_head(k, p["k_norm"])
    if rope and positions is not None:
        sections = cfg.m_rope_sections if cfg.m_rope else None
        q = layers.apply_rope(q, positions, cfg.rope_theta, sections)
        k = layers.apply_rope(k, positions, cfg.rope_theta, sections)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "kv_seq", "kv_heads", "head_dim")
    return q, k, v


# -- masks -------------------------------------------------------------------

def make_mask(q_pos, kv_pos, causal: bool,
              window: Optional[int] = None,
              kv_valid: Optional[jnp.ndarray] = None):
    """Boolean (B, Sq, Skv) mask; True = attend.

    q_pos: (B, Sq) token positions of the queries.
    kv_pos: (B, Skv) positions of the keys (cache slots for decode).
    window: sliding-window size (attend iff 0 <= q-k < window).
    kv_valid: (B, Skv) validity of cache slots (decode ring buffers).
    """
    diff = q_pos[:, :, None] - kv_pos[:, None, :]     # (B, Sq, Skv)
    mask = jnp.ones(diff.shape, bool)
    if causal:
        mask &= diff >= 0
    if window is not None:
        mask &= diff < window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    return mask


# -- core attention -----------------------------------------------------------

def _gqa_scores(q, k, softcap):
    """(B,Sq,KVH,G,hd) x (B,Skv,KVH,hd) -> fp32 (B,KVH,G,Sq,Skv)."""
    s = jnp.einsum("bqhgk,bshk->bhgqs", q, k).astype(jnp.float32)
    return layers.softcap(s, softcap)


def _attend_block(cfg: ModelConfig, q, k, v, mask,
                  scale: Optional[float] = None):
    """Dense attention for one (whole or chunked) query block.

    q: (B,Sq,H,hd) k,v: (B,Skv,KVH,hd) mask: (B,Sq,Skv) -> (B,Sq,H,hd)
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
    qg = q.reshape(b, sq, kvh, g, hd) * scale
    s = _gqa_scores(qg, k, cfg.attn_softcap)               # (B,KVH,G,Sq,Skv)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqs,bshk->bqhgk", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, v.shape[-1])   # v head dim (MLA: != qk dim)


def attention(cfg: ModelConfig, q, k, v, *,
              q_pos, kv_pos, causal: bool = True,
              window: Optional[int] = None,
              kv_valid: Optional[jnp.ndarray] = None,
              impl: str = "dense", chunk: int = 1024,
              scale: Optional[float] = None, unroll: bool = False,
              causal_kv_trim: bool = False):
    """Multi-head attention over explicit q/k/v.

    impl="dense"   full score tensor.
    impl="chunked" query chunks of size ``chunk``: the live score tensor
                   is (B, H, chunk, Skv) and the chunk body is
                   jax.checkpoint'ed so backward recomputes scores instead
                   of saving a per-chunk stack (flash-style memory).
    impl="pallas"  flash-attention kernel (full-causal self-attn only).

    unroll=True replaces the chunk lax.scan with a Python loop (roofline
    probes — scan bodies are cost-counted once by XLA).
    causal_kv_trim=True (unrolled causal self-attention only) slices K/V
    per query chunk to the causally-visible prefix, skipping the fully
    masked upper-triangle blocks (~2x score FLOPs at long S).
    """
    if impl == "pallas":
        # The flash kernel reconstructs the mask from block indices; it
        # cannot honor a kv_valid mask (decode ring buffers, padded
        # cross-attention memories) or non-causal attention.  Refuse
        # loudly instead of returning wrong numbers with those args
        # silently dropped.
        if kv_valid is not None:
            raise ValueError(
                "attention(impl='pallas') cannot honor kv_valid masks — "
                "use impl='dense'/'chunked', or the flash-decode kernel "
                "(kernels/decode_attention) for single-token decode")
        if not causal:
            raise ValueError(
                "attention(impl='pallas') is causal self-attention only; "
                "cross-attention must use impl='dense' or 'chunked'")
        from repro.kernels.flash_attention import ops as fa_ops
        if scale is None:
            scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar
                                    or cfg.head_dim)
        return fa_ops.flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_softcap, scale=scale)
    if impl == "dense" or q.shape[1] <= chunk:
        mask = make_mask(q_pos, kv_pos, causal, window, kv_valid)
        return _attend_block(cfg, q, k, v, mask, scale)
    if impl != "chunked":
        raise ValueError(f"unknown attention impl {impl!r}")

    b, sq, h, hd = q.shape
    n_chunks, rem = divmod(sq, chunk)
    if rem:
        raise ValueError(f"seq {sq} not divisible by chunk {chunk}")

    def chunk_body(qc, qpc, kc, vc, kv_pos_c, kv_valid_c):
        mask = make_mask(qpc, kv_pos_c, causal, window, kv_valid_c)
        return _attend_block(cfg, qc, kc, vc, mask, scale)

    chunk_body = jax.checkpoint(chunk_body)

    if unroll:
        outs = []
        for i in range(n_chunks):
            sl = slice(i * chunk, (i + 1) * chunk)
            if causal_kv_trim and causal and kv_valid is None:
                kv_hi = (i + 1) * chunk
                outs.append(chunk_body(
                    q[:, sl], q_pos[:, sl], k[:, :kv_hi], v[:, :kv_hi],
                    kv_pos[:, :kv_hi], None))
            else:
                outs.append(chunk_body(q[:, sl], q_pos[:, sl], k, v,
                                       kv_pos, kv_valid))
        out = jnp.concatenate(outs, axis=1)
        return shard(out, "batch", "seq", "heads", "head_dim")

    qs = q.reshape(b, n_chunks, chunk, h, hd).swapaxes(0, 1)
    qp = q_pos.reshape(b, n_chunks, chunk).swapaxes(0, 1)

    def step(_, qc_qpc):
        qc, qpc = qc_qpc
        return None, chunk_body(qc, qpc, k, v, kv_pos, kv_valid)

    _, out = jax.lax.scan(step, None, (qs, qp))
    out = out.swapaxes(0, 1).reshape(b, sq, h, v.shape[-1])
    return shard(out, "batch", "seq", "heads", "head_dim")


def output_proj(p, o):
    dt = o.dtype
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return shard(out, "batch", "seq", "d_model")


# -- full self-attention block (no cache) ---------------------------------------

def self_attention(cfg: ModelConfig, p, x, positions, *,
                   causal: bool = True, window: Optional[int] = None,
                   impl: str = "dense", chunk: int = 1024):
    q, k, v = project_qkv(cfg, p, x, positions)
    pos1d = positions[..., 0] if positions.ndim == 3 else positions
    o = attention(cfg, q, k, v, q_pos=pos1d, kv_pos=pos1d, causal=causal,
                  window=window, impl=impl, chunk=chunk)
    return output_proj(p, o)


def cross_attention(cfg: ModelConfig, p, x, enc_out,
                    enc_valid: Optional[jnp.ndarray] = None,
                    impl: str = "dense", chunk: int = 1024):
    """Whisper-style cross attention (no RoPE, no causality)."""
    b, sq = x.shape[:2]
    skv = enc_out.shape[1]
    q, k, v = project_qkv(cfg, p, x, None, kv_x=enc_out, rope=False)
    q_pos = jnp.zeros((b, sq), jnp.int32)
    kv_pos = jnp.zeros((b, skv), jnp.int32)
    o = attention(cfg, q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False,
                  kv_valid=enc_valid, impl=impl, chunk=chunk)
    return output_proj(p, o)


# -- KV cache -------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int] = None,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Cache for one attention layer.

    Full layers: (B, max_len, KVH, hd) k/v. Sliding-window layers use a
    ring buffer of size ``window`` instead (gemma2 local layers) — decode
    memory stays O(window).

    ``cfg.kv_quant`` switches the layout to quantized codes (int8 /
    fp8_e4m3 — see ``kernels/quant``) plus per-(token, kv-head) float32
    absmax scales in ``k_scale``/``v_scale`` (B, size, KVH) leaves;
    ``dtype`` then only names the full-precision layout other engines
    would have used (the code dtype is fixed by the mode).
    """
    size = min(max_len, window) if window else max_len
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    # k and v must be DISTINCT buffers: the serve engine donates cache
    # trees into jitted steps (chunked prefill, row insert), and a
    # buffer shared by two donated leaves gets handed out twice —
    # silent corruption once both outputs land in it.
    if cfg.kv_quant is not None:
        qdt = quant.quant_dtype(cfg.kv_quant)
        return {"k": jnp.zeros(shape, qdt), "v": jnp.zeros(shape, qdt),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_spec_axes() -> Tuple[Optional[str], ...]:
    return ("batch", "kv_seq", "kv_heads", "head_dim")


def scale_spec_axes() -> Tuple[Optional[str], ...]:
    """Logical axes of the quantized layouts' scale leaves."""
    return ("batch", "kv_seq", "kv_heads")


def decode_self_attention(cfg: ModelConfig, p, x, cache, cur_len, *,
                          window: Optional[int] = None,
                          cache_impl: str = "auto",
                          impl: str = "dense"):
    """One-token decode against a cache.

    x: (B, 1, d). cache: {"k","v"} (B, C, KVH, hd). cur_len: count of
    tokens already in the cache (== position of the new token) — either
    a scalar (synchronized decode, every row at the same position) or a
    (B,) vector (continuous batching, per-slot position counters; the
    new k/v land at a *different* cache offset per row via the
    ``kernels/cache_update`` scatter).

    impl: "dense" attends over the whole cache with an explicit masked
    timeline; "flash" routes through ``kernels/decode_attention`` —
    slot->position arithmetic moves in-kernel, no (B, C) position or
    validity tensors are built, the cache is consumed in its own dtype
    (no cache-wide upcast copy), and KV blocks beyond each row's valid
    prefix are never read.
    Returns (out (B,1,d), new_cache).
    """
    b = x.shape[0]
    cur = jnp.asarray(cur_len, jnp.int32)
    per_row = cur.ndim == 1
    positions = cur[:, None] if per_row else jnp.full((b, 1), cur, jnp.int32)
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = project_qkv(cfg, p, x, positions, rope=cfg.use_rope)

    mode = cfg.kv_quant
    ks = vs = None
    cache_size = cache["k"].shape[1]
    if per_row:
        from repro.kernels.cache_update import ops as cu_ops
        slot_rows = (cur % cache_size) if window \
            else jnp.minimum(cur, cache_size - 1)
        if mode is not None:
            k, ks = cu_ops.quant_cache_update(
                cache["k"], cache["k_scale"], k_new, slot_rows, mode,
                impl=cache_impl)
            v, vs = cu_ops.quant_cache_update(
                cache["v"], cache["v_scale"], v_new, slot_rows, mode,
                impl=cache_impl)
        else:
            k = cu_ops.cache_update(cache["k"], k_new, slot_rows,
                                    impl=cache_impl)
            v = cu_ops.cache_update(cache["v"], v_new, slot_rows,
                                    impl=cache_impl)
    else:
        slot = (cur_len % cache_size) if window else cur_len
        if mode is not None:
            k_codes, k_sc = quant.quantize(k_new, mode)
            v_codes, v_sc = quant.quantize(v_new, mode)
            k = jax.lax.dynamic_update_slice(cache["k"], k_codes,
                                             (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(cache["v"], v_codes,
                                             (0, slot, 0, 0))
            ks = jax.lax.dynamic_update_slice(cache["k_scale"], k_sc,
                                              (0, slot, 0))
            vs = jax.lax.dynamic_update_slice(cache["v_scale"], v_sc,
                                              (0, slot, 0))
        else:
            k = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    k = shard(k, *cache_spec_axes())
    v = shard(v, *cache_spec_axes())
    new_cache = {"k": k, "v": v}
    if mode is not None:
        ks = shard(ks, *scale_spec_axes())
        vs = shard(vs, *scale_spec_axes())
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs

    if impl == "flash":
        from repro.kernels.decode_attention import ops as da_ops
        scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
        o = da_ops.decode_attention(
            q, k, v, cur, ring=window is not None,
            softcap=cfg.attn_softcap, scale=scale, k_scale=ks, v_scale=vs)
        return output_proj(p, o), new_cache
    if impl != "dense":
        raise ValueError(f"unknown decode attention impl {impl!r}")

    # Per-slot timeline against the new token's position.  The row
    # dimension is degenerate — (1, C) — when cur_len is a scalar
    # (every row at the same position): the boolean mask broadcasts
    # inside attention, so the scalar path never materialises B copies
    # of the same timeline.
    slots = jnp.arange(cache_size, dtype=jnp.int32)[None]        # (1,C)
    cur_col = cur[:, None] if per_row else cur[None, None]   # (B,1)/(1,1)
    if window:
        # ring buffer: slot s holds the largest position p <= cur with
        # p % size == s, i.e. p = cur - ((cur - s) mod size); negative p
        # means the slot has never been written.
        kv_pos = cur_col - jnp.mod(cur_col - slots, cache_size)
        kv_valid = kv_pos >= 0
        kv_pos = jnp.maximum(kv_pos, 0)
    else:
        kv_pos = slots
        kv_valid = slots <= cur_col

    if mode is not None:
        k_att = quant.dequantize(k, ks).astype(q.dtype)
        v_att = quant.dequantize(v, vs).astype(q.dtype)
    else:
        k_att, v_att = k.astype(q.dtype), v.astype(q.dtype)
    o = attention(cfg, q, k_att, v_att,
                  q_pos=cur_col, kv_pos=kv_pos, causal=True, window=window,
                  kv_valid=kv_valid, impl="dense")
    return output_proj(p, o), new_cache


# -- paged KV cache (block pools + page-table indirection) --------------------

def init_paged_kv_pools(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Physical page pools for one attention layer.

    (P, page_size, KVH, hd) k/v — every layer's pool shares ONE page-id
    space: a request's single (NB,) page-table row addresses the same
    physical page index in every leaf, so the host allocator hands out
    one page id per ``page_size`` token positions across the whole
    stack.  Page 0 is the scratch page (all masked/pad writes land
    there; its content is undefined).  Sliding-window layers store
    their positions *unwrapped* (slot == position) with the window as
    an explicit attention mask — no ring arithmetic, so prefix pages
    are position-stable and shareable across requests.

    ``cfg.kv_quant`` pages the scale leaves exactly like their code
    leaves — (P, page_size, KVH) float32 through the same page tables —
    so a page's scales travel with it through prefix sharing, adoption,
    and eviction.
    """
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    # distinct buffers — donated cache trees must not share (see
    # init_kv_cache)
    if cfg.kv_quant is not None:
        qdt = quant.quant_dtype(cfg.kv_quant)
        return {"k": jnp.zeros(shape, qdt), "v": jnp.zeros(shape, qdt),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_decode_self_attention(cfg: ModelConfig, p, x, cache, layer,
                                cur_len, page_table, *,
                                window: Optional[int] = None,
                                cache_impl: str = "auto"):
    """One-token decode against a *paged* cache.

    x: (B, 1, d).  cache: {"k","v"} (L, P, page_size, KVH, hd) pools of
    a layer stack, of which this layer is ``layer`` (int32 scalar, may
    be traced): read and written in place, never sliced out.
    cur_len: (B,) per-row position counters (paged serving is always
    continuous).  page_table: (B, NB) int32 — rows the scheduler has
    masked to 0 (mid-prefill / dead slots) read and write only the
    scratch page, so their garbage decode tokens cannot touch a live
    request's pages.  Returns (out (B,1,d), new_cache).
    """
    from repro.kernels.cache_update import ops as cu_ops
    from repro.kernels.decode_attention import ops as da_ops
    b = x.shape[0]
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    positions = cur[:, None]
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = project_qkv(cfg, p, x, positions, rope=cfg.use_rope)

    mode = cfg.kv_quant
    ks = vs = None
    ones = jnp.ones((b,), jnp.int32)
    if mode is not None:
        k, ks = cu_ops.quant_paged_cache_update(
            cache["k"], cache["k_scale"], layer, k_new, page_table, cur,
            ones, mode, impl=cache_impl)
        v, vs = cu_ops.quant_paged_cache_update(
            cache["v"], cache["v_scale"], layer, v_new, page_table, cur,
            ones, mode, impl=cache_impl)
    else:
        k = cu_ops.paged_cache_update(cache["k"], layer, k_new, page_table,
                                      cur, ones, impl=cache_impl)
        v = cu_ops.paged_cache_update(cache["v"], layer, v_new, page_table,
                                      cur, ones, impl=cache_impl)
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
    o = da_ops.decode_attention_paged(
        q, k, v, layer, page_table, cur, window=window,
        softcap=cfg.attn_softcap, scale=scale, k_scale=ks, v_scale=vs)
    new_cache = {"k": k, "v": v}
    if mode is not None:
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs
    return output_proj(p, o), new_cache


def paged_prefill_chunk_self_attention(cfg: ModelConfig, p, x, cache, layer,
                                       offset, valid_len, page_table, *,
                                       window: Optional[int] = None,
                                       cache_impl: str = "auto"):
    """One chunk of chunked prefill through one attention layer, paged.

    x: (B, T, d) at absolute positions ``offset[b] + i``; layer
    ``layer`` of the stacked (L, P, page_size, KVH, hd) cache pools
    holds positions ``< offset[b]`` of every row through page_table
    (B, NB).  ``offset`` and ``valid_len`` are (B,) int32 — rows with
    ``valid_len == 0`` (slots decoding, or idle, during this batched
    admission dispatch) contribute garbage outputs the caller discards
    and write nothing (their scatter is fully masked to the scratch
    page).  Returns (out (B, T, d), new_cache).
    """
    from repro.kernels.cache_update import ops as cu_ops
    from repro.kernels.prefill_attention import ops as pf_ops
    b, t = x.shape[:2]
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    positions = off[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[..., None], (b, t, 3))
    q, k_new, v_new = project_qkv(cfg, p, x, positions, rope=cfg.use_rope)

    mode = cfg.kv_quant
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
    o = pf_ops.prefill_attention_paged(
        q, k_new, v_new, cache["k"], cache["v"], layer, page_table, off,
        window=window, softcap=cfg.attn_softcap, scale=scale,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"))
    valids = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    if mode is not None:
        k, ks = cu_ops.quant_paged_cache_update(
            cache["k"], cache["k_scale"], layer, k_new, page_table, off,
            valids, mode, impl=cache_impl)
        v, vs = cu_ops.quant_paged_cache_update(
            cache["v"], cache["v_scale"], layer, v_new, page_table, off,
            valids, mode, impl=cache_impl)
        return output_proj(p, o), {"k": k, "v": v,
                                   "k_scale": ks, "v_scale": vs}
    k = cu_ops.paged_cache_update(cache["k"], layer, k_new, page_table, off,
                                  valids, impl=cache_impl)
    v = cu_ops.paged_cache_update(cache["v"], layer, v_new, page_table, off,
                                  valids, impl=cache_impl)
    return output_proj(p, o), {"k": k, "v": v}


def chunk_kv_write(cache, new, offset, valid_len, *,
                   ring: bool = False):
    """Write a prefill chunk's KV into a cache: ``new[:, t]`` lands at
    position ``offset + t`` (slot ``(offset + t) % C`` when ``ring``)
    for every ``t < valid_len``.

    cache: (B, C, *rest).  new: (B, T, *rest).  offset: scalar or (B,)
    int32 (the chunk's first absolute position).  valid_len: traced
    scalar — tokens beyond it are the right-padding of a final partial
    chunk.

    The scalar-offset full cache takes the fast path: pads land at
    slots past the prompt, which stay invalid under every decode
    path's ``cur_len`` masking until a real decode token overwrites
    them, so the whole chunk lands in one ``dynamic_update_slice``.
    Everything else (ring caches — where a pad write would wrap onto a
    *valid* older position inside the window — and per-row offsets)
    goes through one vectorized gather+select over the C cache slots:
    per slot, the index of the last valid chunk token that maps there
    falls out of the ring arithmetic in closed form, so there is no
    per-token write loop to trace (chunk-sized HLO) or serialize at
    runtime, and a chunk longer than the ring degrades gracefully to
    its surviving tail.
    """
    b, t = new.shape[:2]
    c = cache.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    per_row = offset.ndim == 1
    new = new.astype(cache.dtype)
    if not ring and not per_row:
        starts = (0, offset) + (0,) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new, starts)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    slots = jnp.arange(c, dtype=jnp.int32)[None]           # (1, C)
    off = offset[:, None] if per_row else offset[None, None]
    if ring:
        # slot s's final occupant is the LAST valid chunk token at a
        # position == s (mod C): position p = last_valid - ((last_valid
        # - s) mod C), chunk index i = p - offset; i < 0 means no valid
        # token wrapped onto s — keep the old row.
        last_valid = off + valid_len - 1
        i = (valid_len - 1) - jnp.mod(last_valid - slots, c)
        keep_new = i >= 0
    else:
        i = slots - off
        keep_new = (i >= 0) & (i < valid_len)
    i = jnp.broadcast_to(jnp.clip(i, 0, t - 1), (b, c))
    expand = (...,) + (None,) * (cache.ndim - 2)
    gathered = jnp.take_along_axis(new, i[expand], axis=1)
    return jnp.where(jnp.broadcast_to(keep_new, (b, c))[expand],
                     gathered, cache)


def prefill_chunk_self_attention(cfg: ModelConfig, p, x, cache, offset,
                                 valid_len, *,
                                 window: Optional[int] = None):
    """One chunk of chunked prefill through one attention layer.

    x: (B, T, d) — the chunk's hidden states at absolute positions
    ``offset + i``.  cache: {"k","v"} (B, C, KVH, hd) holding positions
    ``< offset`` (the previous chunks).  offset: scalar or (B,) int32;
    valid_len: traced scalar — tokens ``>= valid_len`` are the final
    partial chunk's right-padding (their outputs are garbage the caller
    discards; their KV is masked out of ring caches and lands on
    never-valid slots of full ones).

    Attention runs through ``kernels/prefill_attention``: one online
    softmax over [cache prefix ++ causal in-chunk keys], with cache
    blocks beyond ``offset`` never read (Pallas on TPU, fused masked
    lax elsewhere — ``PMT_PREFILL_ATTENTION_DISPATCH`` overrides).
    Returns (out (B, T, d), new_cache).
    """
    from repro.kernels.prefill_attention import ops as pf_ops
    b, t = x.shape[:2]
    off = jnp.asarray(offset, jnp.int32)
    positions = (off[:, None] if off.ndim else off) \
        + jnp.arange(t, dtype=jnp.int32)[None]             # (B|1, T)
    positions = jnp.broadcast_to(positions, (b, t))
    if cfg.m_rope:
        positions = jnp.broadcast_to(positions[..., None], (b, t, 3))
    q, k_new, v_new = project_qkv(cfg, p, x, positions, rope=cfg.use_rope)

    mode = cfg.kv_quant
    ring = window is not None
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)
    o = pf_ops.prefill_attention(
        q, k_new, v_new, cache["k"], cache["v"], off,
        ring=ring, window=window, softcap=cfg.attn_softcap, scale=scale,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"))
    if mode is not None:
        # quantize the whole chunk once; codes and scales then ride the
        # same masked ring write (scales are just (B, T, KVH) "rows")
        k_new, k_sc = quant.quantize(k_new, mode)
        v_new, v_sc = quant.quantize(v_new, mode)
    k = chunk_kv_write(cache["k"], k_new, off, valid_len, ring=ring)
    v = chunk_kv_write(cache["v"], v_new, off, valid_len, ring=ring)
    k = shard(k, *cache_spec_axes())
    v = shard(v, *cache_spec_axes())
    new_cache = {"k": k, "v": v}
    if mode is not None:
        ks = chunk_kv_write(cache["k_scale"], k_sc, off, valid_len,
                            ring=ring)
        vs = chunk_kv_write(cache["v_scale"], v_sc, off, valid_len,
                            ring=ring)
        new_cache["k_scale"] = shard(ks, *scale_spec_axes())
        new_cache["v_scale"] = shard(vs, *scale_spec_axes())
    return output_proj(p, o), new_cache


def prefill_kv_cache(cfg: ModelConfig, k, v, max_len: int,
                     window: Optional[int] = None, dtype=jnp.bfloat16):
    """Build a cache from prefill-computed k/v (B, S, KVH, hd).

    ``cfg.kv_quant`` quantizes the whole prefill K/V once and applies
    the identical tail/roll/slice logic to codes and scales — per-row
    quantization commutes with any position-axis shuffle."""
    b, s = k.shape[:2]
    cache = init_kv_cache(cfg, b, max_len, window=window, dtype=dtype)
    size = cache["k"].shape[1]
    if cfg.kv_quant is not None:
        kc, ksc = quant.quantize(k, cfg.kv_quant)
        vc, vsc = quant.quantize(v, cfg.kv_quant)
        leaves = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
    else:
        leaves = {"k": k.astype(dtype), "v": v.astype(dtype)}
    if window and s > size:
        # keep the last `size` positions, ring-aligned so that position p
        # lives at slot p % size.
        start = s - size
        shift = start % size
        return {name: jnp.roll(x[:, start:], shift, axis=1)
                for name, x in leaves.items()}
    return {name: jax.lax.dynamic_update_slice(
                cache[name], x, (0,) * cache[name].ndim)
            for name, x in leaves.items()}
