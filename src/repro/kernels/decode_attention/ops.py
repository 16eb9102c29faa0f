"""Model-facing flash-decode wrapper.

``decode_attention`` accepts the framework's decode layout — new-token
queries (B, 1, H, hdq) against an already-updated cache
k: (B, C, KVH, hdq) / v: (B, C, KVH, hdv) — reshapes q to the kernel's
GQA-packed (B, KVH, G, hdq), and routes to:

  * ``pallas``           the flash-decode kernel (TPU),
  * ``pallas_interpret`` the same kernel in interpret mode (CPU parity
                         testing),
  * ``lax``              a length-aware masked XLA fallback: the cache
                         is cut into 8 static *segments*; each segment
                         computes a masked online-softmax partial
                         (m, l, acc) under a ``lax.cond`` that skips
                         segments entirely beyond the batch-max
                         ``cur_len``, and the partials merge with the
                         standard flash rescaling.  Static segment
                         slices fuse into clean batched GEMMs (better
                         cache locality than one cache-wide sweep), so
                         at fill f the path reads ~f bytes, not C —
                         the kernel's bandwidth saving expressed in
                         plain XLA.

``impl="auto"`` picks Pallas iff the default backend is TPU; the env
var ``PMT_DECODE_ATTENTION_DISPATCH`` (values: pallas /
pallas_interpret / lax) overrides "auto" for experiments.  This is the
*kernel dispatch* knob — the model-level dense-vs-flash choice is
``cfg.decode_attn_impl`` / ``PMT_DECODE_ATTN_IMPL`` (see
blocks.decode_attn_impl), which decides whether this module is called
at all.

Numerics: the Pallas kernel is bit-exact against the blockwise ref.py
oracle (same op-for-op online softmax; see ref.py).  The lax path uses
segment-sized blocks instead of ``block_k``-sized ones, so it matches
within fp reassociation (~1 ulp of fp32 softmax), and is invariant to
how many segments ran: a skipped segment's partial is the identity
under the merge.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.constants import NEG_INF
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_paged_pallas, decode_attention_pallas)
from repro.kernels.decode_attention.ref import pool_heads, take_pages


def _resolve(impl: str) -> str:
    if impl == "auto":
        impl = os.environ.get("PMT_DECODE_ATTENTION_DISPATCH", "auto")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "lax"
    return impl


_LAX_SEGMENTS = 8


def decode_attention_lax(q, k, v, lens, *, ring: bool = False,
                         softcap=None, scale: float = 1.0,
                         block_k: int = 128, v_width=None,
                         k_scale=None, v_scale=None):
    """Length-aware masked decode attention in plain XLA.

    Same layout as the kernel: q (B, KVH, G, hdq), k/v (B, C, KVH, *),
    lens (B,).  The cache is cut into ``_LAX_SEGMENTS`` static
    segments; segments beyond the batch-max ``cur_len`` are skipped by
    ``lax.cond`` (their partial is the merge identity), so the read
    granularity is ~C/8 regardless of cache size.  ``block_k`` is the
    Pallas tiling knob and is unused here.

    K/V segments are transposed to (B, KVH, S, hd) fp32 before the
    score/value contractions — one fused cast+transpose copy of the
    *segment only*, turning both contractions into clean batched GEMMs
    (measurably faster than einsum-ing the strided cache layout, and
    segment-sized working sets stay cache-resident between the score
    and value passes).

    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row absmax scales
    when k/v hold quantized codes — each live segment dequantizes its
    own slice during the cast+transpose copy, so the skipped-segment
    bandwidth saving applies to quantized reads too.
    """
    del block_k                     # kernel tiling knob; segments are ~C/8
    b, kvh, g, _ = q.shape
    c = k.shape[1]
    hdv = v_width if v_width is not None else v.shape[-1]
    qs = q.astype(jnp.float32) * scale
    lens = jnp.asarray(lens, jnp.int32)
    alias = v is k
    quantized = k_scale is not None
    s_alias = v_scale is None or v_scale is k_scale
    if quantized and v_scale is None:
        v_scale = k_scale
    seg = -(-c // _LAX_SEGMENTS)

    def seg_partial(kp, vp, ksp, vsp, lo):
        kf = kp.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,KVH,S,hdq)
        if quantized:
            kf = kf * ksp.transpose(0, 2, 1).astype(jnp.float32)[..., None]
        if v_width is not None and vp is kp and (not quantized or s_alias):
            vf = kf[..., :v_width]
        else:
            vf = vp.transpose(0, 2, 1, 3).astype(jnp.float32)
            if quantized:
                vf = vf * vsp.transpose(0, 2, 1) \
                    .astype(jnp.float32)[..., None]
            if v_width is not None:
                vf = vf[..., :v_width]
        s = jnp.einsum("bhgd,bhkd->bhgk", qs, kf)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        cols = lo + jnp.arange(kp.shape[1], dtype=jnp.int32)[None, None,
                                                             None]
        cur = lens[:, None, None, None]
        if ring:
            valid = jnp.mod(cur - cols, c) <= cur
        else:
            valid = cols <= cur
        s = jnp.where(valid, s, NEG_INF)
        # a row fully masked within a live segment yields m == NEG_INF
        # and garbage l/acc — both are annihilated by exp(m - m_final)
        # underflowing to exactly 0.0 in the merge.
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bhgk,bhkd->bhgd", p, vf)
        return m, l, acc

    # every valid slot of every row lies below ``need``: a row's valid
    # positions are <= lens[b], and a wrapped ring (lens >= C) needs
    # the full cache, which min(lens, C-1) selects.
    need = jnp.minimum(jnp.max(lens), c - 1) + 1
    skip = (jnp.full((b, kvh, g, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, 1), jnp.float32),
            jnp.zeros((b, kvh, g, hdv), jnp.float32))
    parts = []
    for lo in range(0, c, seg):
        kp = k[:, lo:lo + seg]
        vp = kp if alias else v[:, lo:lo + seg]
        if quantized:
            ksp = k_scale[:, lo:lo + seg]
            vsp = ksp if s_alias else v_scale[:, lo:lo + seg]
        else:
            ksp = vsp = None
        if lo == 0:                 # slot 0 is always valid
            parts.append(seg_partial(kp, vp, ksp, vsp, 0))
            continue
        parts.append(jax.lax.cond(
            need > lo,
            lambda kp_, vp_, lo_=lo, ks_=ksp, vs_=vsp:
                seg_partial(kp_, vp_, ks_, vs_, lo_),
            lambda kp_, vp_: skip, kp, vp))
    ms = jnp.stack([p[0] for p in parts])
    m = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m)             # (S, B, KVH, G, 1); skipped -> 0.0
    l = jnp.sum(w * jnp.stack([p[1] for p in parts]), axis=0)
    acc = jnp.sum(w * jnp.stack([p[2] for p in parts]), axis=0)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def decode_attention_paged_lax(q, k_pool, v_pool, layer, page_table, lens, *,
                               window=None, softcap=None, scale: float = 1.0,
                               v_width=None, k_scale=None, v_scale=None):
    """Length-aware masked *paged* decode attention in plain XLA.

    q (B, KVH, G, hdq); stacked pools (L, P, page_size, KVH, *) read at
    layer ``layer``; page_table (B, NB); lens (B,).  Same segment scheme
    as ``decode_attention_lax`` but each live segment first gathers its
    pages through the page table (the gather is the XLA spelling of the
    kernel's index-map indirection, and — like the kernel's clamp — it
    only happens for segments the ``lax.cond`` actually runs, so the
    read/copy volume still tracks the batch-max fill, not the pool
    size).  Paged caches
    are unwrapped: sliding windows arrive as the explicit ``window``
    mask, which also lets segments wholly below the batch-min window
    start skip.
    """
    b, kvh, g, _ = q.shape
    ps = k_pool.shape[2]
    nb = page_table.shape[1]
    c = nb * ps
    hdv = v_width if v_width is not None else v_pool.shape[-1]
    qs = q.astype(jnp.float32) * scale
    lens = jnp.asarray(lens, jnp.int32)
    pt = page_table.astype(jnp.int32)
    alias = v_pool is k_pool
    quantized = k_scale is not None
    s_alias = v_scale is None or v_scale is k_scale
    if quantized and v_scale is None:
        v_scale = k_scale
    seg_pages = -(-nb // _LAX_SEGMENTS)

    def seg_partial(pages, lo):
        kp = take_pages(k_pool, layer, pages)    # (B, sp, ps, KVH, hd)
        sp = pages.shape[1] * ps
        kf = kp.reshape(b, sp, kvh, -1).transpose(0, 2, 1, 3) \
            .astype(jnp.float32)                 # (B, KVH, S, hdq)
        if quantized:
            ksp = take_pages(k_scale, layer, pages)
            kf = kf * ksp.reshape(b, sp, kvh).transpose(0, 2, 1) \
                .astype(jnp.float32)[..., None]
        if alias and (not quantized or s_alias):
            vf = kf[..., :hdv]
        else:
            vp = take_pages(v_pool, layer, pages)
            vf = vp.reshape(b, sp, kvh, -1).transpose(0, 2, 1, 3) \
                .astype(jnp.float32)
            if quantized:
                vsp = take_pages(v_scale, layer, pages)
                vf = vf * vsp.reshape(b, sp, kvh).transpose(0, 2, 1) \
                    .astype(jnp.float32)[..., None]
            vf = vf[..., :hdv]
        s = jnp.einsum("bhgd,bhkd->bhgk", qs, kf)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        cols = lo + jnp.arange(sp, dtype=jnp.int32)[None, None, None]
        cur = lens[:, None, None, None]
        valid = cols <= cur
        if window is not None:
            valid &= (cur - cols) < window
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bhgk,bhkd->bhgd", p, vf)
        return m, l, acc

    need = jnp.minimum(jnp.max(lens), c - 1) + 1
    front = None
    if window is not None:
        front = jnp.maximum(jnp.min(lens) - (window - 1), 0)
    skip = (jnp.full((b, kvh, g, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, 1), jnp.float32),
            jnp.zeros((b, kvh, g, hdv), jnp.float32))
    parts = []
    for pg_lo in range(0, nb, seg_pages):
        pages = pt[:, pg_lo:pg_lo + seg_pages]
        lo = pg_lo * ps
        hi = lo + pages.shape[1] * ps - 1
        live = need > lo if lo else None
        if front is not None:
            f = front <= hi
            live = f if live is None else live & f
        if live is None:                # first segment, no window: always
            parts.append(seg_partial(pages, 0))
            continue
        parts.append(jax.lax.cond(
            live,
            lambda pages_, lo_=lo: seg_partial(pages_, lo_),
            lambda pages_: skip, pages))
    ms = jnp.stack([p[0] for p in parts])
    m = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m)             # (S, B, KVH, G, 1); skipped -> 0.0
    l = jnp.sum(w * jnp.stack([p[1] for p in parts]), axis=0)
    acc = jnp.sum(w * jnp.stack([p[2] for p in parts]), axis=0)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def decode_attention_paged(q, k_pool, v_pool, layer, page_table, cur_len, *,
                           window=None, softcap=None, scale: float = 1.0,
                           v_width=None, k_scale=None, v_scale=None,
                           impl: str = "auto"):
    """One-token decode attention over a *paged* cache.

    q: (B, 1, H, hdq) new-token queries.  k_pool/v_pool:
    (L, P, page_size, KVH, hd*) a layer stack's physical pages shared by
    all rows, or (L, P, page_size, hd*) for one kv head stored without
    a head axis (the MLA latent pool, read in place), *after* the new
    token's k/v landed (``paged_cache_update``);
    layer ``layer`` (int32 scalar, may be traced) is read in place.
    page_table: (B, NB) int32 logical block -> physical page.  cur_len:
    (B,) int32.  Paged caches store sliding-window layers unwrapped, so
    ``window`` is an explicit mask here (no ``ring``).  ``v_width`` as
    in ``decode_attention``.  ``k_scale``/``v_scale``: (L, P, page_size,
    KVH) float32 per-row scale pools when the code pools are quantized
    (``v_scale`` defaults to ``k_scale`` — the MLA aliased cache).
    Returns (B, 1, H, hdv) in q.dtype.
    """
    impl = _resolve(impl)
    b, sq, h, hdq = q.shape
    if sq != 1:
        raise ValueError(f"decode_attention_paged takes one query token, "
                         f"got Sq={sq}")
    kvh = pool_heads(k_pool)
    if h % kvh:
        raise ValueError(f"H={h} not divisible by KVH={kvh}")
    g = h // kvh
    qg = q.reshape(b, kvh, g, hdq)
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    kw = dict(window=window, softcap=softcap, scale=scale, v_width=v_width,
              k_scale=k_scale, v_scale=v_scale)
    if impl == "lax":
        out = decode_attention_paged_lax(qg, k_pool, v_pool, layer,
                                         page_table, lens, **kw)
    elif impl in ("pallas", "pallas_interpret"):
        out = decode_attention_paged_pallas(
            qg, k_pool, v_pool, layer, page_table, lens,
            interpret=impl == "pallas_interpret", **kw)
    else:
        raise ValueError(f"unknown decode_attention impl {impl!r}")
    return out.reshape(b, 1, h, out.shape[-1])


def decode_attention(q, k, v, cur_len, *, ring: bool = False,
                     softcap=None, scale: float = 1.0,
                     block_k: int = 128, v_width=None,
                     k_scale=None, v_scale=None,
                     impl: str = "auto"):
    """One-token decode attention over a full cache.

    q: (B, 1, H, hdq) new-token queries.  k: (B, C, KVH, hdq) and
    v: (B, C, KVH, hdv): the cache *after* the new token's k/v landed at
    its slot.  cur_len: scalar or (B,) int32 — the new token's position
    == tokens already in the cache (valid cache positions are
    ``<= cur_len``).  ``ring=True`` for sliding-window ring-buffer
    caches.  ``v_width``: v is the first ``v_width`` lanes of the given
    array (which may be k itself — the MLA concatenated latent cache).
    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row absmax scales
    when k/v hold quantized codes (see ``kernels/quant``; ``v_scale``
    defaults to ``k_scale`` — the MLA aliased cache quantizes once).
    Returns (B, 1, H, hdv) in q.dtype; k/v are consumed in their own
    dtype (no cache-wide upcast copy, and quantized caches are
    dequantized blockwise in-register, never materialised).
    """
    impl = _resolve(impl)
    b, sq, h, hdq = q.shape
    if sq != 1:
        raise ValueError(f"decode_attention takes one query token, got "
                         f"Sq={sq}")
    kvh = k.shape[2]
    if h % kvh:
        raise ValueError(f"H={h} not divisible by KVH={kvh}")
    g = h // kvh
    qg = q.reshape(b, kvh, g, hdq)
    lens = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    kw = dict(ring=ring, softcap=softcap, scale=scale, block_k=block_k,
              v_width=v_width, k_scale=k_scale, v_scale=v_scale)
    if impl == "lax":
        out = decode_attention_lax(qg, k, v, lens, **kw)
    elif impl in ("pallas", "pallas_interpret"):
        out = decode_attention_pallas(
            qg, k, v, lens, interpret=impl == "pallas_interpret", **kw)
    else:
        raise ValueError(f"unknown decode_attention impl {impl!r}")
    return out.reshape(b, 1, h, out.shape[-1])
