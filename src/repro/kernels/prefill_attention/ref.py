"""Pure-jnp oracle for the chunked-prefill flash kernel.

Like ``decode_attention/ref.py``, this is the kernel's *blockwise twin*:
it sweeps the already-written cache prefix block by block, then the
chunk's own keys block by block, folding every block into the same
(m, l, acc) online-softmax accumulator with the same operations in the
same order.  Fully-masked blocks are bit-neutral updates (masked scores
are ``NEG_INF``, whose exp underflows to exactly 0.0 against any live
running max, and whose garbage contribution while the max is still
``NEG_INF`` is annihilated — multiplied by an exactly-0.0 alpha — the
moment a live block arrives).  The oracle processes *every* block; the
Pallas kernel skips cache blocks beyond each row's prefix, so the two
must agree bitwise (asserted in tests/test_prefill_attention.py).

Semantics (matching the serve engine's chunked admission):

  * Query ``i`` of row ``b`` sits at absolute position ``offs[b] + i``.
  * ``k_cache``/``v_cache`` is the cache *before* this chunk's KV lands:
    it holds positions ``< offs[b]`` only.
      - ``ring=False``: slot ``s`` holds position ``s``; attendable iff
        ``s < offs[b]`` (the chunk's own keys arrive separately).
      - ``ring=True`` (sliding-window ring of size ``C``): slot ``s``
        holds position ``p = (offs[b]-1) - ((offs[b]-1-s) mod C)``;
        attendable iff ``p >= 0`` and ``pos_q - p < window``.  Unlike
        decode — where the single query is the newest token and the
        window mask is subsumed by the ring size — chunk queries
        *trail* the prefix by up to ``chunk-1`` positions, so the
        explicit window mask is load-bearing here.
  * ``k_chunk``/``v_chunk`` are the chunk's own keys/values at positions
    ``offs[b] + j``; query ``i`` attends ``j <= i`` (and, windowed,
    ``i - j < window``).  Right-padding a final partial chunk is the
    *caller's* contract: pad queries produce garbage rows that are
    discarded, and causality keeps real queries off pad keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.constants import DEFAULT_BLOCK_K, NEG_INF
from repro.kernels.decode_attention.ref import pick_block_k, take_pages

__all__ = ["prefill_attention_ref", "pick_block_k"]


def _fold_block(q, k_blk, v_blk, valid, m, l, acc, *, softcap):
    """Fold one key block into the online-softmax accumulator.

    q: (B, KVH, T, G, hdq) fp32, pre-scaled.  k_blk: (B, bk, KVH, hdq),
    v_blk: (B, bk, KVH, hdv) in cache dtype.  valid: (B, 1, T, 1, bk)
    bool.  m, l: (B, KVH, T, G, 1) fp32.  acc: (B, KVH, T, G, hdv) fp32.
    """
    s = jnp.einsum("bhtgd,bkhd->bhtgk", q, k_blk.astype(jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(valid, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = alpha * acc + jnp.einsum("bhtgk,bkhd->bhtgd", p,
                                       v_blk.astype(jnp.float32))
    return m_new, l_new, acc_new


def _cache_valid(offs, cols, q_pos, *, cache_size, ring, window):
    """(B, 1, T, 1, bk) mask for cache slots ``cols`` against chunk
    queries at ``q_pos``.  offs: (B,), cols: (bk,), q_pos: (T,).

    ``ring=False`` with ``window`` set is the *unwrapped* sliding-window
    layout the paged cache uses: slot == position, window as an explicit
    mask instead of a ring size."""
    off = offs[:, None, None, None, None]                  # (B,1,1,1,1)
    col = cols[None, None, None, None, :]                  # (1,1,1,1,bk)
    qp = (q_pos[None, :, None] + offs[:, None, None])[:, None, :, :, None]
    if ring:
        last = off - 1
        pos = last - jnp.mod(last - col, cache_size)       # (B,1,1,1,bk)
        valid = (pos >= 0) & (qp - pos < window)
    elif window is not None:
        valid = (col < off) & (qp - col < window)          # (B,1,T,1,bk)
    else:
        valid = jnp.broadcast_to(col < off, qp.shape[:4] + (cols.shape[0],))
    return valid


def _chunk_valid(b, cols, q_idx, *, window):
    """(B, 1, T, 1, bk) causal (and windowed) in-chunk mask."""
    diff = q_idx[:, None] - cols[None, :]                  # (T, bk)
    valid = diff >= 0
    if window is not None:
        valid &= diff < window
    return jnp.broadcast_to(valid[None, None, :, None, :],
                            (b, 1, q_idx.shape[0], 1, cols.shape[0]))


def prefill_attention_ref(q, k_chunk, v_chunk, k_cache, v_cache, offs, *,
                          ring: bool = False, window=None, softcap=None,
                          scale: float = 1.0, block_k: int = DEFAULT_BLOCK_K,
                          k_scale=None, v_scale=None):
    """q: (B, KVH, T, G, hdq); k_chunk/v_chunk: (B, T, KVH, hdq/hdv);
    k_cache/v_cache: (B, C, KVH, hdq/hdv); offs: scalar or (B,) int32.
    Returns (B, KVH, T, G, hdv) in q.dtype.

    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row absmax scales
    when the *cache* holds quantized codes (the chunk's own k/v are
    always full precision) — dequantized per cache block with the exact
    op order of the kernel's in-register dequant (``v_scale`` defaults
    to ``k_scale`` — the MLA aliased cache quantizes once)."""
    b, kvh, t, g, _ = q.shape
    c = k_cache.shape[1]
    hdv = v_cache.shape[-1]
    bk_c = pick_block_k(c, block_k)
    bk_t = pick_block_k(t, block_k)
    qs = q.astype(jnp.float32) * scale
    offs = jnp.broadcast_to(jnp.asarray(offs, jnp.int32), (b,))
    q_idx = jnp.arange(t, dtype=jnp.int32)
    if k_scale is not None and v_scale is None:
        v_scale = k_scale

    m = jnp.full((b, kvh, t, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, t, g, 1), jnp.float32)
    acc = jnp.zeros((b, kvh, t, g, hdv), jnp.float32)

    def cache_body(j, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_cache, j * bk_c, bk_c, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_cache, j * bk_c, bk_c, axis=1)
        if k_scale is not None:
            ks_blk = jax.lax.dynamic_slice_in_dim(k_scale, j * bk_c, bk_c,
                                                  axis=1)
            vs_blk = jax.lax.dynamic_slice_in_dim(v_scale, j * bk_c, bk_c,
                                                  axis=1)
            k_blk = k_blk.astype(jnp.float32) * \
                ks_blk[..., None].astype(jnp.float32)
            v_blk = v_blk.astype(jnp.float32) * \
                vs_blk[..., None].astype(jnp.float32)
        cols = j * bk_c + jnp.arange(bk_c, dtype=jnp.int32)
        valid = _cache_valid(offs, cols, q_idx, cache_size=c, ring=ring,
                             window=window)
        return _fold_block(qs, k_blk, v_blk, valid, m, l, acc,
                           softcap=softcap)

    def chunk_body(j, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k_chunk, j * bk_t, bk_t, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_chunk, j * bk_t, bk_t, axis=1)
        cols = j * bk_t + jnp.arange(bk_t, dtype=jnp.int32)
        valid = _chunk_valid(b, cols, q_idx, window=window)
        return _fold_block(qs, k_blk, v_blk, valid, m, l, acc,
                           softcap=softcap)

    # The oracle sweeps EVERY block — cache prefix first, then the
    # chunk — through the same fold the implementations use, so the
    # comparison is exact: block skipping is the only thing the Pallas
    # kernel adds.
    m, l, acc = jax.lax.fori_loop(0, c // bk_c, cache_body, (m, l, acc))
    m, l, acc = jax.lax.fori_loop(0, t // bk_t, chunk_body, (m, l, acc))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def prefill_attention_paged_ref(q, k_chunk, v_chunk, k_pool, v_pool,
                                layer, page_table, offs, *, window=None,
                                softcap=None, scale: float = 1.0,
                                v_width=None, k_scale=None, v_scale=None):
    """Blockwise twin of the *paged* chunked-prefill kernel.

    q: (B, KVH, T, G, hdq); k_chunk/v_chunk: (B, T, KVH, *);
    k_pool/v_pool: (L, P, page_size, KVH, *) stacked physical pages, of
    which layer ``layer`` is read (``v_pool`` may be ``k_pool`` with
    ``v_width`` — MLA); page_table: (B, NB); offs: (B,) int32 chunk
    start positions.

    Gathers the logical cache view through the page table and sweeps it
    with cache blocks of exactly one page — the paged kernel's blocking
    — so pages it skips (beyond each row's prefix, or wholly below the
    window) are bit-neutral folds and the comparison is bitwise.  Paged
    caches are unwrapped: ``window`` is an explicit mask, never a ring.
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
    if v_width is not None:
        v_cache = v_cache[..., :v_width]
        v_chunk = v_chunk[..., :v_width]
    ks = vs = None
    if k_scale is not None:
        ks = take_pages(k_scale, layer, pt).reshape(b, nb * ps, kvh)
        if v_scale is None or v_scale is k_scale:
            vs = ks
        else:
            vs = take_pages(v_scale, layer, pt).reshape(b, nb * ps, kvh)
    return prefill_attention_ref(q, k_chunk, v_chunk, k_cache, v_cache,
                                 offs, ring=False, window=window,
                                 softcap=softcap, scale=scale, block_k=ps,
                                 k_scale=ks, v_scale=vs)
