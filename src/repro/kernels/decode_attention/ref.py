"""Pure-jnp oracle for the flash-decode kernel.

The oracle is the kernel's *blockwise twin*, not a dense softmax: it
sweeps the cache in the same ``block_k`` blocks, applies the same
masking, and folds each block into the same (m, l, acc) online-softmax
accumulator with the same operations in the same order.  Skipping a
fully-masked block and processing it are bit-identical updates (masked
scores are ``NEG_INF``, whose exp underflows to exactly 0.0 and leaves
m/l/acc untouched), so the oracle — which processes *every* block — is
an exact-parity reference for the Pallas kernel, which skips blocks
beyond ``cur_len`` (the kernel-vs-ref tests assert bitwise equality).

The segmented ``ops.decode_attention_lax`` fallback implements the same
masking semantics at segment granularity with a different (fused)
compute layout, so it is held to fp-reassociation tolerance against
this oracle rather than bitwise equality — see
tests/test_decode_attention.py.

Semantics (matching ``models.attention.decode_self_attention``):

  * ``lens[b]`` is the position of row ``b``'s new token == the count
    of tokens already in the cache; the cache has already absorbed the
    new k/v at its slot, so valid slots are exactly positions
    ``<= lens[b]``.
  * ``ring=False``: slot ``s`` holds position ``s``; valid iff
    ``s <= lens[b]``.
  * ``ring=True`` (sliding-window ring buffer of size ``C ==
    min(max_len, window)``): slot ``s`` holds the largest position
    ``p <= cur`` with ``p % C == s``; valid iff ``p >= 0``, i.e.
    ``(cur - s) mod C <= cur``.  The window mask itself is subsumed:
    every held position is within ``C - 1 <= window - 1`` of the query.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.constants import NEG_INF


def pick_block_k(cache_size: int, block_k: int) -> int:
    """Largest divisor of ``cache_size`` no bigger than ``block_k``.

    Cache sizes are normally powers of two (max_len / window), so this
    returns ``block_k`` itself; odd sizes degrade to a smaller even
    split instead of requiring padding.
    """
    return math.gcd(min(block_k, cache_size), cache_size)


def pool_heads(pool) -> int:
    """Kv heads of a stacked paged pool: (L, P, page_size, KVH, hd), or
    1 for a pool stored without a head axis, (L, P, page_size, hd)."""
    return pool.shape[3] if pool.ndim == 5 else 1


def take_pages(pool, layer, pages):
    """Pages ``pages`` (int32, any shape) of layer ``layer`` of a stacked
    (L, P, page_size, ...) pool, gathered from the whole stack: the
    layer offsets the page ids, so no layer-sized slice is taken."""
    n, p = pool.shape[:2]
    flat = pool.reshape(n * p, *pool.shape[2:])
    return jnp.take(flat, pages + jnp.asarray(layer, jnp.int32) * p, axis=0)


def _block_step(q, k_blk, v_blk, k_lo, lens, m, l, acc, *,
                cache_size: int, ring: bool, softcap, window=None,
                ks_blk=None, vs_blk=None):
    """Fold one kv block into the online-softmax accumulator.

    q: (B, KVH, G, hdq) fp32, pre-scaled.  k_blk: (B, bk, KVH, hdq),
    v_blk: (B, bk, KVH, hdv) in cache dtype.  k_lo: first cache slot of
    the block (python int or traced scalar).  lens: (B,) int32.
    m, l: (B, KVH, G, 1) fp32 running max/sum.  acc: (B, KVH, G, hdv)
    fp32.  Returns the updated (m, l, acc).

    ``window`` (non-ring only) masks positions below ``cur - window + 1``
    — the *unwrapped* sliding-window layout the paged cache uses, where
    slot ``s`` always holds position ``s`` and the window is an explicit
    mask instead of a ring size.

    ``ks_blk``/``vs_blk``: (B, bk, KVH) float32 per-row absmax scales
    when k_blk/v_blk hold quantized codes — dequantized here with the
    exact op order of the kernel's in-register dequant, keeping the
    blockwise comparison bitwise in the quantized modes too.
    """
    bk = k_blk.shape[1]
    kf = k_blk.astype(jnp.float32)
    if ks_blk is not None:
        kf = kf * ks_blk[..., None].astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", q, kf)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    cols = k_lo + jnp.arange(bk, dtype=jnp.int32)[None, None, None, :]
    cur = lens.astype(jnp.int32)[:, None, None, None]
    if ring:
        valid = jnp.mod(cur - cols, cache_size) <= cur
    else:
        valid = cols <= cur
        if window is not None:
            valid &= (cur - cols) < window
    s = jnp.where(valid, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    vf = v_blk.astype(jnp.float32)
    if vs_blk is not None:
        vf = vf * vs_blk[..., None].astype(jnp.float32)
    acc_new = alpha * acc + jnp.einsum("bhgk,bkhd->bhgd", p, vf)
    return m_new, l_new, acc_new


def decode_attention_ref(q, k, v, lens, *, ring: bool = False,
                         softcap=None, scale: float = 1.0,
                         block_k: int = 128, k_scale=None, v_scale=None):
    """q: (B, KVH, G, hdq), k: (B, C, KVH, hdq), v: (B, C, KVH, hdv),
    lens: scalar or (B,) int32.  Returns (B, KVH, G, hdv) in q.dtype.
    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row absmax scales
    when k/v hold quantized codes (``v_scale`` defaults to ``k_scale``
    — the MLA aliased cache quantizes once)."""
    b, kvh, g, _ = q.shape
    c = k.shape[1]
    hdv = v.shape[-1]
    bk = pick_block_k(c, block_k)
    qs = q.astype(jnp.float32) * scale
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (b,))
    if k_scale is not None and v_scale is None:
        v_scale = k_scale

    def body(j, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * bk, bk, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * bk, bk, axis=1)
        ks_blk = vs_blk = None
        if k_scale is not None:
            ks_blk = jax.lax.dynamic_slice_in_dim(k_scale, j * bk, bk, axis=1)
            vs_blk = jax.lax.dynamic_slice_in_dim(v_scale, j * bk, bk, axis=1)
        return _block_step(qs, k_blk, v_blk, j * bk, lens, m, l, acc,
                           cache_size=c, ring=ring, softcap=softcap,
                           ks_blk=ks_blk, vs_blk=vs_blk)

    m = jnp.full((b, kvh, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, g, 1), jnp.float32)
    acc = jnp.zeros((b, kvh, g, hdv), jnp.float32)
    # The oracle sweeps EVERY block (no length awareness) through the
    # same loop structure as the implementations, so the comparison is
    # exact: block skipping is the only thing the fast paths add.
    m, l, acc = jax.lax.fori_loop(0, c // bk, body, (m, l, acc))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def decode_attention_paged_ref(q, k_pool, v_pool, layer, page_table, lens, *,
                               window=None, softcap=None, scale: float = 1.0,
                               v_width=None, k_scale=None, v_scale=None):
    """Blockwise twin of the *paged* flash-decode kernel.

    q: (B, KVH, G, hdq); k_pool/v_pool: (L, P, page_size, KVH, hd*)
    stacked physical pages, of which layer ``layer`` is read (``v_pool``
    may be ``k_pool`` with ``v_width`` set — the MLA concatenated latent
    cache); page_table: (B, NB) int32; lens: (B,) int32.

    Gathers the logical (B, NB*page_size, KVH, *) view through the page
    table, then folds every page with ``block_k == page_size`` — the
    exact blocking the paged kernel uses, so skipped pages (beyond
    ``lens`` or wholly below the window) are bit-neutral updates and the
    comparison is bitwise, same as the contiguous pair.
    Paged caches are always *unwrapped* (slot == position): sliding
    windows arrive as the explicit ``window`` mask, never ``ring``.
    """
    b, kvh, g, _ = q.shape
    ps = k_pool.shape[2]
    nb = page_table.shape[1]
    c = nb * ps
    pt = page_table.astype(jnp.int32)
    k = take_pages(k_pool, layer, pt).reshape(b, c, kvh, k_pool.shape[-1])
    if v_pool is k_pool:
        v = k
    else:
        v = take_pages(v_pool, layer, pt).reshape(b, c, kvh,
                                                  v_pool.shape[-1])
    if v_width is not None:
        v = v[..., :v_width]
    hdv = v.shape[-1]
    qs = q.astype(jnp.float32) * scale
    lens = jnp.broadcast_to(jnp.asarray(lens, jnp.int32), (b,))
    ks = vs = None
    if k_scale is not None:
        ks = take_pages(k_scale, layer, pt).reshape(b, c, kvh)
        if v_scale is None or v_scale is k_scale:
            vs = ks
        else:
            vs = take_pages(v_scale, layer, pt).reshape(b, c, kvh)

    def body(j, carry):
        m, l, acc = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, j * ps, ps, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, j * ps, ps, axis=1)
        ks_blk = vs_blk = None
        if ks is not None:
            ks_blk = jax.lax.dynamic_slice_in_dim(ks, j * ps, ps, axis=1)
            vs_blk = jax.lax.dynamic_slice_in_dim(vs, j * ps, ps, axis=1)
        return _block_step(qs, k_blk, v_blk, j * ps, lens, m, l, acc,
                           cache_size=c, ring=False, softcap=softcap,
                           window=window, ks_blk=ks_blk, vs_blk=vs_blk)

    m = jnp.full((b, kvh, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, g, 1), jnp.float32)
    acc = jnp.zeros((b, kvh, g, hdv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nb, body, (m, l, acc))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
