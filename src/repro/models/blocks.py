"""Per-layer block assembly for every assigned architecture.

A "block" is one layer of the stack. Its kind is a function of the layer
index and the config:

  "A"  attention + FFN (dense MLP or MoE)   — all transformer archs
  "M"  mamba + FFN (dense MLP or MoE)       — jamba's SSM layers
  "m"  mLSTM block (self-contained)         — xlstm
  "s"  sLSTM block (self-contained)         — xlstm
  "E"  bidirectional encoder block          — whisper encoder
  "X"  decoder block with cross-attention   — whisper decoder

Blocks expose four entry points with a uniform signature so model.py can
scan over homogeneous stacks: init, forward (full sequence), decode (one
token against a cache), and cache init.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers, mamba, mla, moe, xlstm
from repro.sharding.specs import annotate


# -- layer-kind layout ----------------------------------------------------------

def layer_kind(cfg: ModelConfig, idx: int, encoder: bool = False) -> str:
    if encoder:
        return "E"
    if cfg.is_encoder_decoder:
        return "X"
    if cfg.family == "ssm":
        pat = cfg.xlstm.pattern
        return pat[idx % len(pat)]
    if cfg.family == "hybrid":
        return cfg.hybrid_pattern[idx % len(cfg.hybrid_pattern)]
    return "A"


def layer_window(cfg: ModelConfig, idx: int) -> Optional[int]:
    """Sliding-window size for this layer (gemma2 local/global pattern)."""
    if cfg.layer_pattern and cfg.sliding_window:
        kind = cfg.layer_pattern[idx % len(cfg.layer_pattern)]
        return cfg.sliding_window if kind == "L" else None
    return cfg.sliding_window


def attn_impl(cfg: ModelConfig, seq_len: int) -> str:
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    if seq_len <= cfg.attn_chunk or seq_len % cfg.attn_chunk:
        return "dense"   # short or non-chunk-aligned (whisper's 1500)
    return "chunked"


def decode_attn_impl(cfg: ModelConfig) -> str:
    """Resolve ``cfg.decode_attn_impl`` for this process.

    "auto" defers to the ``PMT_DECODE_ATTN_IMPL`` env var (values:
    dense / flash; A/B experiments), then picks "flash" — the
    length-aware ``kernels/decode_attention`` path — iff the default
    backend is TPU, where its Pallas kernel compiles; elsewhere "dense"
    keeps the decode step a single fused XLA region.  Both
    self-attention KV caches and the MLA latent cache honor the knob;
    explicit "flash" off-TPU runs the kernel's masked-lax twin.  (How
    "flash" then dispatches between Pallas and the lax twin is the
    separate ops-layer knob ``PMT_DECODE_ATTENTION_DISPATCH``.)
    """
    impl = cfg.decode_attn_impl
    if impl == "auto":
        impl = os.environ.get("PMT_DECODE_ATTN_IMPL", "auto")
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "dense"
    if impl not in ("dense", "flash"):
        raise ValueError(f"unknown decode_attn_impl {impl!r}")
    return impl


# -- init -----------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, idx: int, encoder: bool = False):
    kind = layer_kind(cfg, idx, encoder)
    ks = jax.random.split(key, 8)
    if kind in ("m", "s"):
        p = {"norm": layers.init_norm(ks[0], cfg)}
        p["cell"] = (xlstm.init_mlstm(ks[1], cfg) if kind == "m"
                     else xlstm.init_slstm(ks[1], cfg))
        return p

    p = {"norm_1": layers.init_norm(ks[0], cfg),
         "norm_2": layers.init_norm(ks[1], cfg)}
    if cfg.post_block_norm:
        p["post_norm_1"] = layers.init_norm(ks[6], cfg)
        p["post_norm_2"] = layers.init_norm(ks[7], cfg)

    if kind == "M":
        p["mixer"] = mamba.init_mamba(ks[2], cfg)
    elif cfg.attention == "mla":
        p["mixer"] = mla.init_mla(ks[2], cfg)
    else:
        p["mixer"] = attn.init_attention(ks[2], cfg)

    if kind == "X":
        p["norm_x"] = layers.init_norm(ks[4], cfg)
        p["cross"] = attn.init_attention(ks[5], cfg, cross=True)

    if kind != "E" and moe.is_moe_layer(cfg, idx):
        p["ffn"] = moe.init_moe(ks[3], cfg)
    else:
        ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.dense_ff_dim and kind != "E":
            ff = cfg.moe.dense_ff_dim
        p["ffn"] = layers.init_mlp(ks[3], cfg, ff=ff)
    return p


# -- forward (full sequence) ------------------------------------------------------

def block_forward(cfg: ModelConfig, p, x, positions, idx: int, *,
                  enc_out=None, encoder: bool = False,
                  collect_kv: bool = False):
    """One block over the full sequence.

    Returns (x, aux_loss, kv) — kv is the mixer state the serve path needs
    to build a cache from prefill (None unless collect_kv).
    """
    kind = layer_kind(cfg, idx, encoder)
    aux = jnp.zeros((), jnp.float32)
    kv = None

    if kind in ("m", "s"):
        h = layers.apply_norm(cfg, p["norm"], x)
        fwd = xlstm.mlstm_forward if kind == "m" else xlstm.slstm_forward
        if collect_kv:
            out, kv = fwd(cfg, p["cell"], h, return_state=True)
            return x + out, aux, kv
        return x + fwd(cfg, p["cell"], h), aux, None

    h = layers.apply_norm(cfg, p["norm_1"], x)
    impl = attn_impl(cfg, h.shape[1])
    if kind == "M":
        if collect_kv:
            out, kv = mamba.mamba_forward(cfg, p["mixer"], h,
                                          return_state=True)
        else:
            out = mamba.mamba_forward(cfg, p["mixer"], h)
    elif cfg.attention == "mla":
        out, kv_pair = mla.mla_self_attention(cfg, p["mixer"], h, positions,
                                              impl=impl, chunk=cfg.attn_chunk)
        kv = kv_pair if collect_kv else None
    else:
        window = layer_window(cfg, idx)
        causal = kind != "E"
        q, k, v = attn.project_qkv(cfg, p["mixer"], h, positions,
                                   rope=cfg.use_rope)
        pos1d = positions[..., 0] if positions.ndim == 3 else positions
        o = attn.attention(cfg, q, k, v, q_pos=pos1d, kv_pos=pos1d,
                           causal=causal, window=window, impl=impl,
                           chunk=cfg.attn_chunk,
                           unroll=cfg.unroll_time_chunks,
                           causal_kv_trim=cfg.causal_kv_trim)
        out = attn.output_proj(p["mixer"], o)
        kv = (k, v) if collect_kv else None
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_1"], out)
    x = x + out

    if kind == "X":
        h = layers.apply_norm(cfg, p["norm_x"], x)
        x = x + attn.cross_attention(cfg, p["cross"], h, enc_out)

    h = layers.apply_norm(cfg, p["norm_2"], x)
    if "router" in p["ffn"] and collect_kv:
        out, _ = moe.moe_serve(cfg, p["ffn"], h)    # serve prefill
    elif "router" in p["ffn"]:
        out, aux = moe.apply_moe(cfg, p["ffn"], h)
    else:
        out = layers.apply_mlp(cfg, p["ffn"], h)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_2"], out)
    return x + out, aux, kv


# -- caches ------------------------------------------------------------------------

def init_block_cache(cfg: ModelConfig, idx: int, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    kind = layer_kind(cfg, idx)
    if kind == "m":
        return xlstm.init_mlstm_cache(cfg, batch, dtype)
    if kind == "s":
        return xlstm.init_slstm_cache(cfg, batch, dtype)
    if kind == "M":
        return mamba.init_mamba_cache(cfg, batch, dtype)
    if cfg.attention == "mla":
        return mla.init_mla_cache(cfg, batch, max_len, dtype)
    window = layer_window(cfg, idx)
    cache = attn.init_kv_cache(cfg, batch, max_len, window=window,
                               dtype=dtype)
    if kind == "X":
        # cross-attention k/v are filled once from the encoder output
        # (distinct buffers — donated cache trees must not share; see
        # attn.init_kv_cache)
        xshape = (batch, cfg.enc_len, cfg.num_kv_heads, cfg.head_dim)
        cache["xk"] = jnp.zeros(xshape, dtype)
        cache["xv"] = jnp.zeros(xshape, dtype)
    return cache


def cache_axes(cfg: ModelConfig, idx: int):
    kind = layer_kind(cfg, idx)
    if kind == "m":
        return xlstm.mlstm_cache_axes()
    if kind == "s":
        return xlstm.slstm_cache_axes()
    if kind == "M":
        return mamba.mamba_cache_axes()
    if cfg.attention == "mla":
        return mla.mla_cache_axes(cfg)
    ax = {"k": attn.cache_spec_axes(), "v": attn.cache_spec_axes()}
    if cfg.kv_quant is not None:
        ax["k_scale"] = attn.scale_spec_axes()
        ax["v_scale"] = attn.scale_spec_axes()
    if kind == "X":
        ax["xk"] = attn.cache_spec_axes()
        ax["xv"] = attn.cache_spec_axes()
    return ax


# -- decode (one token) ---------------------------------------------------------------

def block_decode(cfg: ModelConfig, p, x, cache, cur_len, idx: int):
    """One-token decode through one block. x: (B,1,d)."""
    kind = layer_kind(cfg, idx)
    if kind in ("m", "s"):
        h = layers.apply_norm(cfg, p["norm"], x)
        dec = xlstm.mlstm_decode if kind == "m" else xlstm.slstm_decode
        out, cache = dec(cfg, p["cell"], h, cache)
        return x + out, cache

    h = layers.apply_norm(cfg, p["norm_1"], x)
    if kind == "M":
        out, cache = mamba.mamba_decode(cfg, p["mixer"], h, cache)
    elif cfg.attention == "mla":
        out, cache = mla.mla_decode_attention(cfg, p["mixer"], h, cache,
                                              cur_len,
                                              impl=decode_attn_impl(cfg))
    else:
        window = layer_window(cfg, idx)
        kv_cache = {n: cache[n] for n in ("k", "v", "k_scale", "v_scale")
                    if n in cache}
        out, kv_cache = attn.decode_self_attention(
            cfg, p["mixer"], h, kv_cache, cur_len, window=window,
            impl=decode_attn_impl(cfg))
        cache = dict(cache, **kv_cache)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_1"], out)
    x = x + out

    if kind == "X":
        h = layers.apply_norm(cfg, p["norm_x"], x)
        b = h.shape[0]
        q, _, _ = attn.project_qkv(cfg, p["cross"], h, None, rope=False)
        skv = cache["xk"].shape[1]
        o = attn.attention(
            cfg, q, cache["xk"].astype(q.dtype), cache["xv"].astype(q.dtype),
            q_pos=jnp.zeros((b, 1), jnp.int32),
            kv_pos=jnp.zeros((b, skv), jnp.int32), causal=False, impl="dense")
        x = x + attn.output_proj(p["cross"], o)

    h = layers.apply_norm(cfg, p["norm_2"], x)
    if "router" in p["ffn"]:
        out, _ = moe.moe_serve(cfg, p["ffn"], h)
    else:
        out = layers.apply_mlp(cfg, p["ffn"], h)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_2"], out)
    return x + out, cache


# -- chunked prefill (resume from a partial cache at an offset) ------------------------

def _masked_state_scan(cell_fn, x, cache, valid_len):
    """Scan a one-token decode cell over a chunk, freezing the carried
    state at pad positions.

    ``cell_fn(x_t (B,1,d), cache) -> (out (B,1,d), new_cache)`` is the
    cell's existing decode recurrence — chunked prefill for state
    blocks (mamba / mLSTM / sLSTM) is exactly the decode scan resumed
    from the carried cache, so prefix-resume costs nothing new.  Steps
    ``t >= valid_len`` (a final partial chunk's right-padding) keep the
    previous state: the carry a later decode resumes from reflects the
    real prompt only.  Pad outputs are garbage the caller discards.
    """
    t = x.shape[1]

    def step(carry, xt_i):
        xt, i = xt_i
        out, new = cell_fn(xt[:, None], carry)
        keep = i < valid_len
        carry = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new, carry)
        return carry, out[:, 0]

    # Some cells widen state leaves on their first step (sLSTM keeps h
    # in compute dtype while the stored cache is bf16); promote the
    # carry up front so the scan sees one stable dtype per leaf, and
    # demote on exit so chunk N+1's input cache matches chunk N's —
    # the serve engine jits one chunk function and feeds caches back
    # through it, so the cache tree must be a dtype fixpoint.
    orig = cache
    new_struct = jax.eval_shape(lambda c: cell_fn(x[:, :1], c)[1], cache)
    cache = jax.tree.map(lambda o, s: o.astype(s.dtype), cache, new_struct)
    idx = jnp.arange(t, dtype=jnp.int32)
    cache, ys = jax.lax.scan(step, cache, (x.swapaxes(0, 1), idx))
    cache = jax.tree.map(lambda n, o: n.astype(o.dtype), cache, orig)
    return ys.swapaxes(0, 1), cache


def block_prefill_chunk(cfg: ModelConfig, p, x, cache, offset, valid_len,
                        idx: int):
    """One prefill chunk through one block. x: (B, T, d) at absolute
    positions ``offset + i``; ``cache`` holds the state/KV of positions
    ``< offset``; ``valid_len`` marks a final partial chunk's real
    length.  Returns (x, new_cache) — same contract as ``block_decode``
    widened to T tokens."""
    kind = layer_kind(cfg, idx)
    if kind == "X":
        raise NotImplementedError(
            "chunked prefill does not cover encoder-decoder archs (the "
            "cross-attention KV comes from one whole-encoder pass); "
            "serve admission falls back to blocking prefill for them")
    if kind in ("m", "s"):
        h = layers.apply_norm(cfg, p["norm"], x)
        dec = xlstm.mlstm_decode if kind == "m" else xlstm.slstm_decode
        out, cache = _masked_state_scan(
            lambda xt, c: dec(cfg, p["cell"], xt, c), h, cache, valid_len)
        return x + out, cache

    h = layers.apply_norm(cfg, p["norm_1"], x)
    if kind == "M":
        out, cache = _masked_state_scan(
            lambda xt, c: mamba.mamba_decode(cfg, p["mixer"], xt, c),
            h, cache, valid_len)
    elif cfg.attention == "mla":
        out, cache = mla.mla_prefill_chunk(cfg, p["mixer"], h, cache,
                                           offset, valid_len)
    else:
        window = layer_window(cfg, idx)
        kv_cache = {n: cache[n] for n in ("k", "v", "k_scale", "v_scale")
                    if n in cache}
        out, kv_cache = attn.prefill_chunk_self_attention(
            cfg, p["mixer"], h, kv_cache, offset, valid_len,
            window=window)
        cache = dict(cache, **kv_cache)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_1"], out)
    x = x + out

    h = layers.apply_norm(cfg, p["norm_2"], x)
    if "router" in p["ffn"]:
        out, _ = moe.moe_serve(cfg, p["ffn"], h)
    else:
        out = layers.apply_mlp(cfg, p["ffn"], h)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_2"], out)
    return x + out, cache


# -- paged decode / prefill (block pools + page tables) --------------------------------

def init_paged_block_cache(cfg: ModelConfig, idx: int, num_pages: int,
                           page_size: int,
                           dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Paged pools for one block.  Only attention-kind layers ("A",
    including MLA) page — state blocks carry O(1) recurrent state and
    encoder-decoder blocks a one-shot cross cache, neither of which
    a page table buys anything for (``model.supports_paged`` gates
    whole-model eligibility)."""
    if layer_kind(cfg, idx) != "A":
        raise ValueError(f"layer {idx} (kind {layer_kind(cfg, idx)!r}) "
                         "has no paged cache layout")
    if cfg.attention == "mla":
        return mla.init_paged_mla_pool(cfg, num_pages, page_size, dtype)
    return attn.init_paged_kv_pools(cfg, num_pages, page_size, dtype)


def paged_cache_axes(cfg: ModelConfig, idx: int):
    """Logical axes for paged pool leaves — no batch axis (the pool's
    leading dim is physical pages shared by every slot)."""
    if cfg.attention == "mla":
        ax = {"kv": ("kv_pages", "page", "kv_rank")}
        if cfg.kv_quant is not None:
            ax["kv_scale"] = ("kv_pages", "page")
        return ax
    ax = ("kv_pages", "page", "kv_heads", "head_dim")
    axes = {"k": ax, "v": ax}
    if cfg.kv_quant is not None:
        sax = ("kv_pages", "page", "kv_heads")
        axes["k_scale"] = sax
        axes["v_scale"] = sax
    return axes


def _block_tail(cfg: ModelConfig, p, x, out, valid):
    """Shared post-mixer tail: post-norm, residual, FFN (dense or MoE).
    Returns (x, counts): a MoE layer's ``moe_serve`` assignment counts
    of the ``valid`` (B, S) rows, None for a dense FFN."""
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_1"], out)
    x = x + out
    h = layers.apply_norm(cfg, p["norm_2"], x)
    counts = None
    if "router" in p["ffn"]:
        out, counts = moe.moe_serve(cfg, p["ffn"], h, valid)
    else:
        out = layers.apply_mlp(cfg, p["ffn"], h)
    if cfg.post_block_norm:
        out = layers.apply_norm(cfg, p["post_norm_2"], out)
    return x + out, counts


def block_paged_decode(cfg: ModelConfig, p, x, cache, layer, cur_len,
                       page_table, idx: int, live=None):
    """One-token decode through one block against paged pools.
    x: (B,1,d); cache: stacked (L, P, ...) pools, this block's at index
    ``layer``; cur_len: (B,); page_table: (B, NB); ``live`` (B,) bool,
    the rows that decode a real token (all when None).  Returns (x,
    cache, counts) — ``counts`` as ``_block_tail``'s, over the live
    rows."""
    h = layers.apply_norm(cfg, p["norm_1"], x)
    if cfg.attention == "mla":
        out, cache = mla.mla_paged_decode_attention(cfg, p["mixer"], h,
                                                    cache, layer, cur_len,
                                                    page_table)
    else:
        out, cache = attn.paged_decode_self_attention(
            cfg, p["mixer"], h, cache, layer, cur_len, page_table,
            window=layer_window(cfg, idx))
    x, counts = _block_tail(cfg, p, x, out,
                            None if live is None else live[:, None])
    return x, cache, counts


def block_paged_prefill_chunk(cfg: ModelConfig, p, x, cache, layer, offset,
                              valid_len, page_table, idx: int):
    """One prefill chunk through one block against paged pools.
    x: (B, T, d); cache: stacked (L, P, ...) pools, this block's at
    index ``layer``; offset/valid_len: (B,); page_table: (B, NB).
    Returns (x, cache, counts), counts over each row's first
    ``valid_len`` tokens."""
    h = layers.apply_norm(cfg, p["norm_1"], x)
    if cfg.attention == "mla":
        out, cache = mla.mla_paged_prefill_chunk(cfg, p["mixer"], h, cache,
                                                 layer, offset, valid_len,
                                                 page_table)
    else:
        out, cache = attn.paged_prefill_chunk_self_attention(
            cfg, p["mixer"], h, cache, layer, offset, valid_len, page_table,
            window=layer_window(cfg, idx))
    valid = jnp.arange(x.shape[1], dtype=jnp.int32)[None] \
        < jnp.reshape(valid_len, (-1, 1))
    x, counts = _block_tail(cfg, p, x, out, valid)
    return x, cache, counts


# -- prefill cache construction --------------------------------------------------------

def prefill_block_cache(cfg: ModelConfig, idx: int, kv, max_len: int,
                        x_enc_kv=None, dtype=jnp.bfloat16):
    """Build this block's decode cache from prefill-collected state."""
    kind = layer_kind(cfg, idx)
    if kind in ("m", "s", "M"):
        raise ValueError("state blocks build caches inside prefill")
    if cfg.attention == "mla":
        latent, k_rope = kv
        return mla.prefill_mla_cache(cfg, latent, k_rope, max_len, dtype)
    k, v = kv
    window = layer_window(cfg, idx)
    cache = attn.prefill_kv_cache(cfg, k, v, max_len, window=window,
                                  dtype=dtype)
    if kind == "X" and x_enc_kv is not None:
        cache["xk"], cache["xv"] = (z.astype(dtype) for z in x_enc_kv)
    return cache
