"""Model-facing chunked-prefill attention wrapper.

``prefill_attention`` accepts the framework's chunk layout — chunk
queries (B, T, H, hdq) against the chunk's own keys/values
(B, T, KVH, *) plus the request's already-written cache prefix
(B, C, KVH, *) — reshapes q to the kernel's GQA-packed
(B, KVH, T, G, hdq), and routes to:

  * ``pallas``           the chunked-prefill flash kernel (TPU),
  * ``pallas_interpret`` the same kernel in interpret mode (CPU parity
                         testing),
  * ``lax``              a fused masked-XLA fallback: one dense masked
                         softmax over [cache prefix ++ chunk].  Chunked
                         prefill is compute-bound (T queries per call),
                         so the fallback favors one fused XLA region
                         over a segment-skipping sweep (measured
                         faster; decode's single query row is the
                         opposite trade — see decode_attention_lax);
                         it matches the oracle within fp32 softmax
                         reassociation (~1 ulp).

``impl="auto"`` picks Pallas iff the default backend is TPU; the env
var ``PMT_PREFILL_ATTENTION_DISPATCH`` (values: pallas /
pallas_interpret / lax) overrides "auto" for experiments.

Numerics: the Pallas kernel is bit-exact against the blockwise ref.py
oracle (same op-for-op online softmax; skipped cache blocks are
bit-neutral updates — see ref.py).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.constants import DEFAULT_BLOCK_K, NEG_INF
from repro.kernels.decode_attention.ref import pool_heads, take_pages
from repro.kernels.prefill_attention.prefill_attention import (
    prefill_attention_paged_pallas, prefill_attention_pallas)


def _resolve(impl: str) -> str:
    if impl == "auto":
        impl = os.environ.get("PMT_PREFILL_ATTENTION_DISPATCH", "auto")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "lax"
    return impl


def prefill_attention_lax(q, k_chunk, v_chunk, k_cache, v_cache, offs, *,
                          ring: bool = False, window=None, softcap=None,
                          scale: float = 1.0, block_k: int = DEFAULT_BLOCK_K,
                          v_width=None, k_scale=None, v_scale=None):
    """Fused masked chunk attention in plain XLA.

    Same layout as the kernel: q (B, KVH, T, G, hdq), chunk k/v
    (B, T, KVH, *), cache k/v (B, C, KVH, *), offs (B,).  One dense
    masked softmax over [cache prefix ++ chunk]: chunked prefill is
    compute-bound (T queries per call), and on CPU/GPU-via-XLA the
    single fused region beats a segment-skipping sweep — T-row masks
    and per-segment rescaling cost more than the elided reads save
    (measured; decode, with its single query row, is the opposite
    case).  Length-aware read elision is the Pallas kernel's job.
    ``block_k`` is the Pallas tiling knob and is unused here.

    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row scales when the
    *cache* holds quantized codes (chunk k/v stay full precision) — the
    cache is dequantized with the shared block scales before the fused
    softmax, so the lax path agrees with the blockwise twins to fp
    reassociation like the unquantized case.
    """
    del block_k
    b, kvh, t, g, _ = q.shape
    c = k_cache.shape[1]
    if v_width is not None:
        v_cache = v_cache[..., :v_width]
        v_chunk = v_chunk[..., :v_width]
    if k_scale is not None:
        vs = k_scale if v_scale is None else v_scale
        k_cache = k_cache.astype(jnp.float32) * \
            k_scale[..., None].astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32) * \
            vs[..., None].astype(jnp.float32)
    qs = q.astype(jnp.float32) * scale
    offs = jnp.asarray(offs, jnp.int32)
    k_all = jnp.concatenate([k_cache, k_chunk], axis=1)    # (B, C+T, KVH, *)
    v_all = jnp.concatenate([v_cache, v_chunk], axis=1)
    s = jnp.einsum("bhtgd,bshd->bhtgs", qs, k_all.astype(jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    off = offs[:, None, None, None, None]                  # (B,1,1,1,1)
    q_pos = jnp.arange(t, dtype=jnp.int32)[None, None, :, None, None] + off
    slots = jnp.arange(c, dtype=jnp.int32)[None, None, None, None, :]
    if ring:
        last = off - 1
        pos = last - jnp.mod(last - slots, c)
        cache_ok = (pos >= 0) & (q_pos - pos < window)     # (B,1,T,1,C)
    elif window is not None:
        # unwrapped sliding window (paged layout): slot == position,
        # window applied as an explicit mask
        cache_ok = (slots < off) & (q_pos - slots < window)
    else:
        cache_ok = jnp.broadcast_to(slots < off, (b, 1, t, 1, c))
    diff = (jnp.arange(t, dtype=jnp.int32)[:, None]
            - jnp.arange(t, dtype=jnp.int32)[None, :])     # (T, T)
    chunk_ok = diff >= 0
    if window is not None:
        chunk_ok &= diff < window
    chunk_ok = jnp.broadcast_to(chunk_ok[None, None, :, None, :],
                                (b, 1, t, 1, t))
    valid = jnp.concatenate([cache_ok, chunk_ok], axis=-1)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtgs,bshd->bhtgd", p, v_all.astype(jnp.float32))
    return o.astype(q.dtype)


def prefill_attention_paged_lax(q, k_chunk, v_chunk, k_pool, v_pool,
                                layer, page_table, offs, *, window=None,
                                softcap=None, scale: float = 1.0,
                                v_width=None, k_scale=None, v_scale=None):
    """Fused masked *paged* chunk attention in plain XLA.

    Gathers the logical (B, NB*page_size, KVH, *) cache view of layer
    ``layer`` of the stacked pools through the page table — the XLA
    spelling of the kernel's index-map indirection — then runs the same
    fused masked softmax as ``prefill_attention_lax`` (chunked prefill
    is compute-bound, so the one-gather copy is in the noise next to
    the T-query matmuls).  Paged caches are unwrapped: ``window`` is an
    explicit mask.
    """
    b, kvh, t, g, _ = q.shape
    ps = k_pool.shape[2]
    nb = page_table.shape[1]
    pt = page_table.astype(jnp.int32)
    k_cache = take_pages(k_pool, layer, pt).reshape(b, nb * ps, kvh,
                                                    k_pool.shape[-1])
    if v_pool is k_pool:
        v_cache = k_cache
    else:
        v_cache = take_pages(v_pool, layer, pt).reshape(b, nb * ps, kvh,
                                                        v_pool.shape[-1])
    ks = vs = None
    if k_scale is not None:
        ks = take_pages(k_scale, layer, pt).reshape(b, nb * ps, kvh)
        if v_scale is None or v_scale is k_scale:
            vs = ks
        else:
            vs = take_pages(v_scale, layer, pt).reshape(b, nb * ps, kvh)
    return prefill_attention_lax(q, k_chunk, v_chunk, k_cache, v_cache,
                                 offs, ring=False, window=window,
                                 softcap=softcap, scale=scale,
                                 v_width=v_width, k_scale=ks, v_scale=vs)


def prefill_attention_paged(q, k_chunk, v_chunk, k_pool, v_pool, layer,
                            page_table, offset, *, window=None, softcap=None,
                            scale: float = 1.0, v_width=None,
                            k_scale=None, v_scale=None,
                            impl: str = "auto"):
    """Chunked-prefill attention over a *paged* cache prefix.

    q: (B, T, H, hdq) chunk queries at positions ``offset[b] + i``.
    k_chunk/v_chunk: (B, T, KVH, *) — the chunk's own keys/values (NOT
    yet scattered into the pool).  k_pool/v_pool: (L, P, page_size, KVH,
    *) a layer stack's physical pages, or (L, P, page_size, *) for one
    kv head stored without a head axis; layer ``layer`` (int32 scalar,
    may be traced) holds positions ``< offset[b]`` of every row,
    addressed through page_table (B, NB) int32.  offset: scalar or (B,)
    int32.  Paged caches store sliding-window layers unwrapped, so
    ``window`` is an explicit mask (no ``ring``).  ``v_width`` as in
    ``prefill_attention``.  ``k_scale``/``v_scale``: (L, P, page_size,
    KVH) float32 per-row scale pools when the code pools are quantized
    (``v_scale`` defaults to ``k_scale``).  Returns (B, T, H, hdv) in
    q.dtype.
    """
    impl = _resolve(impl)
    b, t, h, hdq = q.shape
    if k_chunk.shape[1] != t:
        raise ValueError(f"chunk keys cover {k_chunk.shape[1]} tokens but "
                         f"the query chunk has {t}")
    kvh = pool_heads(k_pool)
    if h % kvh:
        raise ValueError(f"H={h} not divisible by KVH={kvh}")
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, hdq).transpose(0, 2, 1, 3, 4)
    offs = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    kw = dict(window=window, softcap=softcap, scale=scale, v_width=v_width,
              k_scale=k_scale, v_scale=v_scale)
    if impl == "lax":
        out = prefill_attention_paged_lax(qg, k_chunk, v_chunk, k_pool,
                                          v_pool, layer, page_table, offs,
                                          **kw)
    elif impl in ("pallas", "pallas_interpret"):
        out = prefill_attention_paged_pallas(
            qg, k_chunk, v_chunk, k_pool, v_pool, layer, page_table, offs,
            interpret=impl == "pallas_interpret", **kw)
    else:
        raise ValueError(f"unknown prefill_attention impl {impl!r}")
    hdv = out.shape[-1]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hdv)


def prefill_attention(q, k_chunk, v_chunk, k_cache, v_cache, offset, *,
                      ring: bool = False, window=None, softcap=None,
                      scale: float = 1.0, block_k: int = DEFAULT_BLOCK_K,
                      v_width=None, k_scale=None, v_scale=None,
                      impl: str = "auto"):
    """Chunked-prefill attention: T chunk queries over [prefix ++ chunk].

    q: (B, T, H, hdq) chunk queries at positions ``offset + i``.
    k_chunk/v_chunk: (B, T, KVH, hdq/hdv) — the chunk's own keys/values
    (NOT yet scattered into the cache).  k_cache/v_cache:
    (B, C, KVH, hdq/hdv) — the cache holding positions ``< offset``
    (previous chunks).  offset: scalar or (B,) int32.  ``ring=True``
    for sliding-window ring caches; ``window`` (required with ring) is
    applied explicitly — chunk queries trail the prefix, so the ring
    size does not subsume it the way decode's single newest-token query
    does.  ``v_width``: v operands are the first ``v_width`` lanes of
    the given arrays (which may alias k — the MLA latent cache).
    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row scales when the
    *cache* holds quantized codes (chunk k/v always arrive full
    precision; ``v_scale`` defaults to ``k_scale``).
    Returns (B, T, H, hdv) in q.dtype.
    """
    impl = _resolve(impl)
    b, t, h, hdq = q.shape
    if k_chunk.shape[1] != t:
        raise ValueError(f"chunk keys cover {k_chunk.shape[1]} tokens but "
                         f"the query chunk has {t}")
    kvh = k_cache.shape[2]
    if h % kvh:
        raise ValueError(f"H={h} not divisible by KVH={kvh}")
    if ring and window is None:
        raise ValueError("ring caches need an explicit window")
    if window is not None and not ring:
        raise ValueError("window only applies to ring caches here "
                         "(full-cache layers carry no window)")
    g = h // kvh
    qg = q.reshape(b, t, kvh, g, hdq).transpose(0, 2, 1, 3, 4)
    offs = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))
    kw = dict(ring=ring, window=window, softcap=softcap, scale=scale,
              block_k=block_k, v_width=v_width, k_scale=k_scale,
              v_scale=v_scale)
    if impl == "lax":
        out = prefill_attention_lax(qg, k_chunk, v_chunk, k_cache, v_cache,
                                    offs, **kw)
    elif impl in ("pallas", "pallas_interpret"):
        out = prefill_attention_pallas(
            qg, k_chunk, v_chunk, k_cache, v_cache, offs,
            interpret=impl == "pallas_interpret", **kw)
    else:
        raise ValueError(f"unknown prefill_attention impl {impl!r}")
    hdv = out.shape[-1]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, t, h, hdv)
