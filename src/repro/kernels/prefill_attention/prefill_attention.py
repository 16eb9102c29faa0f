"""Chunked-prefill flash attention for TPU — the serve admission kernel.

Chunked prefill processes a prompt ``chunk`` tokens at a time against
the request's partially-written KV cache, so prefill compiles **once**
(one chunk shape) instead of once per power-of-two prompt bucket, and
the serve scheduler can interleave one chunk between decode steps
instead of stalling the whole live batch for a full prompt.  The kernel
is the admission hot path: a ``(T, G)``-packed query block attending to

  * the **cache prefix** — KV written by previous chunks (positions
    ``< offs[b]``); per-row offsets arrive via scalar prefetch and clamp
    the cache BlockSpec index maps, so cache blocks entirely beyond a
    row's prefix are never read from HBM (the same elision trick as
    ``kernels/decode_attention``) and a ``pl.when`` skips their MXU
    work; and
  * the **chunk's own keys** — passed separately (they have not been
    scattered into the cache yet), causally masked in-kernel.

Grid is (B, cache_steps + chunk_steps) with the kv sweep innermost
(``arbitrary`` semantics).  As in flash-decode, one K/V block spans
every kv head, ``(1, bk, KVH, hd)`` — the TPU tiling rule holds because
its last two dims are the array's — and the kernel walks the heads
in-register; the per-head fp32 (T, G, hdv) accumulators plus running
row-max/row-sum live in VMEM scratch across both phases of the sweep —
one continuous online softmax, so the result is a single attention over
[prefix ++ chunk].  Compiled calls need the cache and chunk block sizes
to be multiples of 8 (or the whole axis), as in flash-decode.

Ring caches (sliding-window layers): slot ``s`` holds position
``(offs-1) - ((offs-1-s) mod C)``.  Chunk queries trail the newest
prefix position by up to ``T-1``, so — unlike decode — the explicit
window mask is applied in-kernel on both phases.

``v_width`` lets V alias K (the MLA [latent | rope] concatenated cache:
scores use the full row, values only the latent prefix).

Quantized caches (``k_scale``/``v_scale`` set): the *cache prefix*
holds int8/fp8_e4m3 codes plus per-(slot, kv head) float32 absmax
scales (see ``kernels/quant``); the chunk's own k/v are still full
precision — they have not been through the quantizing cache write yet.
Scale blocks ride the same clamped cache index maps (minus the lane
axis), so skipped prefix blocks elide the scale DMA too, and the
cache-phase fold dequantizes in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constants import DEFAULT_BLOCK_K, NEG_INF
from repro.kernels.decode_attention.decode_attention import (
    check_block, finish, init, scratch, unpack)
from repro.kernels.prefill_attention.ref import pick_block_k


def _fold_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
                valid, *, scale: float, softcap):
    """Fold one (bk, KVH, hd) key block into every head's online-softmax
    accumulator.  ``valid``: (T, 1, bk) — broadcast over the G axis of
    the scores and shared by all heads.  ``ks_ref``/``vs_ref``: the
    block's (bk, KVH) scales when it holds quantized codes."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32) * scale            # (T, G, hdq)
        k_blk = k_ref[0, :, h, :]
        v_blk = v_ref[0, :, h, :]
        if ks_ref is not None:
            k_blk = k_blk.astype(jnp.float32) * \
                ks_ref[0, :, h:h + 1].astype(jnp.float32)
            v_blk = v_blk.astype(jnp.float32) * \
                vs_ref[0, :, h:h + 1].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (T, G, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                                      # (T, G, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (T, G, hdv)
        acc_ref[h] = alpha * acc_ref[h] + pv
        m_ref[h] = m_new


def _chunk_phase(q_ref, kx_ref, vx_ref, m_ref, l_ref, acc_ref, ki, *,
                 cache_steps: int, bk_t: int, chunk: int, window, scale,
                 softcap):
    """Fold the chunk's own keys (causal; every block holds a key some
    query attends, so none are skippable)."""
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
    j_lo = (ki - cache_steps) * bk_t
    cols = j_lo + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk_t), 2)
    diff = q_idx - cols                                        # (T, 1, bk_t)
    valid = diff >= 0
    if window is not None:
        valid &= diff < window
    _fold_heads(q_ref, kx_ref, vx_ref, None, None, m_ref, l_ref, acc_ref,
                valid, scale=scale, softcap=softcap)


def _prefill_kernel(offs_ref, q_ref, kx_ref, vx_ref, kc_ref, vc_ref, *refs,
                    scale: float, ring: bool, window, softcap,
                    bk_c: int, bk_t: int, cache_steps: int,
                    total_steps: int, cache_size: int, chunk: int,
                    quantized: bool = False):
    kcs_ref, vcs_ref, o_ref, m_ref, l_ref, acc_ref = unpack(refs, quantized)
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    off = offs_ref[bi]

    @pl.when(ki == 0)
    def _():
        init(m_ref, l_ref, acc_ref)

    # -- phase 1: cache prefix.  Blocks whose first slot is at or past
    # the row's written prefix hold nothing attendable (full cache:
    # slots >= off unwritten; ring: min(off, C) covers the not-yet-
    # wrapped tail) — their DMA was elided by the index map, skip the
    # compute as well.
    @pl.when((ki < cache_steps) & (ki * bk_c < jnp.minimum(off, cache_size)))
    def _cache_phase():
        k_lo = ki * bk_c
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk_c), 2)
        q_pos = off + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
        if ring:
            last = off - 1
            pos = last - jnp.mod(last - cols, cache_size)
            valid = (pos >= 0) & (q_pos - pos < window)
        else:
            valid = jnp.broadcast_to(cols < off, (chunk, 1, bk_c))
        _fold_heads(q_ref, kc_ref, vc_ref, kcs_ref, vcs_ref, m_ref, l_ref,
                    acc_ref, valid, scale=scale, softcap=softcap)

    # -- phase 2: the chunk's own keys.
    @pl.when(ki >= cache_steps)
    def _():
        _chunk_phase(q_ref, kx_ref, vx_ref, m_ref, l_ref, acc_ref, ki,
                     cache_steps=cache_steps, bk_t=bk_t, chunk=chunk,
                     window=window, scale=scale, softcap=softcap)

    @pl.when(ki == total_steps - 1)
    def _():
        finish(o_ref, l_ref, acc_ref)


def _paged_prefill_kernel(offs_ref, pt_ref, q_ref, kx_ref, vx_ref, kc_ref,
                          vc_ref, *refs, scale: float, window, softcap,
                          ps: int, bk_t: int, cache_steps: int,
                          total_steps: int, chunk: int,
                          quantized: bool = False):
    kcs_ref, vcs_ref, o_ref, m_ref, l_ref, acc_ref = unpack(refs, quantized)
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    off = offs_ref[bi]

    @pl.when(ki == 0)
    def _():
        init(m_ref, l_ref, acc_ref)

    # -- phase 1: the paged cache prefix.  One block == one physical
    # page; unwrapped layout (slot == position), so beyond-prefix pages
    # and — windowed — pages wholly below the first query's window
    # start are both skippable (their DMA was elided by the index map).
    k_lo = ki * ps
    live = (ki < cache_steps) & (k_lo < off)
    if window is not None:
        live &= (k_lo + ps - 1) >= off - (window - 1)

    @pl.when(live)
    def _cache_phase():
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
        q_pos = off + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
        valid = jnp.broadcast_to(cols < off, (chunk, 1, ps))
        if window is not None:
            valid &= (q_pos - cols) < window
        _fold_heads(q_ref, kc_ref, vc_ref, kcs_ref, vcs_ref, m_ref, l_ref,
                    acc_ref, valid, scale=scale, softcap=softcap)

    # -- phase 2: the chunk's own keys (identical to the contiguous
    # kernel — the chunk is not paged).
    @pl.when(ki >= cache_steps)
    def _():
        _chunk_phase(q_ref, kx_ref, vx_ref, m_ref, l_ref, acc_ref, ki,
                     cache_steps=cache_steps, bk_t=bk_t, chunk=chunk,
                     window=window, scale=scale, softcap=softcap)

    @pl.when(ki == total_steps - 1)
    def _():
        finish(o_ref, l_ref, acc_ref)


def prefill_attention_paged_pallas(q, k_chunk, v_chunk, k_pool, v_pool,
                                   layer, page_table, offs, *, window=None,
                                   softcap=None, scale: float = 1.0,
                                   v_width=None, k_scale=None, v_scale=None,
                                   interpret: bool = False):
    """Paged chunked-prefill: q (B, KVH, T, G, hdq); chunk k/v
    (B, T, KVH, *); the stacked physical pools (L, P, page_size, KVH, *)
    of a layer stack, read at layer ``layer`` (int32 scalar, may be
    traced) through page_table (B, NB) int32; offs (B,) int32.  The
    stack is seen as one pool of ``L * P`` pages (a free reshape, so it
    is read in place) through the page table offset by ``layer * P``.
    The cache-phase BlockSpec index maps read the page table from
    scalar-prefetch SMEM (one block == one page, all kv heads) with the
    same clamp-to-elide-DMA trick as the contiguous kernel.  Paged caches are unwrapped:
    sliding windows arrive as the explicit ``window`` mask, never
    ``ring``.  ``k_scale``/``v_scale``: (L, P, page_size, KVH) float32
    per-row scale pools when the code pools are quantized (chunk k/v
    stay full precision).  Returns (B, KVH, T, G, hdv) in q.dtype."""
    b, kvh, t, g, hdq = q.shape
    p, ps = k_pool.shape[1:3]
    nb = page_table.shape[1]
    c = nb * ps
    hdv = v_width if v_width is not None else v_pool.shape[-1]
    bk_t = pick_block_k(t, ps)       # match the paged ref twin's blocking
    if not interpret:
        check_block(bk_t, t, "paged prefill_attention chunk blocks")
    cache_steps = nb
    chunk_steps = t // bk_t
    total_steps = cache_steps + chunk_steps
    quantized = k_scale is not None
    if quantized and v_scale is None:
        v_scale = k_scale
    flat = lambda pool: pool.reshape((-1,) + pool.shape[2:])
    page_table = page_table.astype(jnp.int32) + \
        jnp.asarray(layer, jnp.int32) * p

    def q_map(bi, ki, offs, pt):
        return (bi, 0, 0, 0, 0)

    def _page(bi, ki, offs, pt):
        # Clamp to the row's needed page range, then go through the
        # page table: revisited physical indices elide the HBM copy
        # (beyond-prefix pages, the whole chunk phase, and — windowed —
        # the below-window head).
        last = jnp.minimum(jnp.maximum(offs[bi] - 1, 0), c - 1) // ps
        j = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(offs[bi] - (window - 1), 0) // ps
            j = jnp.maximum(j, jnp.minimum(first, last))
        return pt[bi, j]

    def cache_map(bi, ki, offs, pt):
        return (_page(bi, ki, offs, pt), 0, 0, 0)

    def scale_map(bi, ki, offs, pt):
        # Same physical page as the codes: scale DMAs elide together.
        return (_page(bi, ki, offs, pt), 0, 0)

    def chunk_map(bi, ki, offs, pt):
        j = jnp.clip(ki - cache_steps, 0, chunk_steps - 1)
        return (bi, j, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kvh, t, g, hdq), q_map),
        pl.BlockSpec((1, bk_t, kvh, hdq), chunk_map),
        pl.BlockSpec((1, bk_t, kvh, hdv), chunk_map),
        pl.BlockSpec((1, ps, kvh, hdq), cache_map),
        pl.BlockSpec((1, ps, kvh, hdv), cache_map),
    ]
    operands = [q, k_chunk, v_chunk, flat(k_pool), flat(v_pool)]
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, kvh), scale_map),
                     pl.BlockSpec((1, ps, kvh), scale_map)]
        operands += [flat(k_scale), flat(v_scale)]

    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, window=window, softcap=softcap,
        ps=ps, bk_t=bk_t, cache_steps=cache_steps, total_steps=total_steps,
        chunk=t, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, total_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, t, g, hdv), q_map),
        scratch_shapes=scratch((kvh, t, g), hdv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t, g, hdv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(offs.astype(jnp.int32), page_table, *operands)


def prefill_attention_pallas(q, k_chunk, v_chunk, k_cache, v_cache, offs, *,
                             ring: bool = False, window=None, softcap=None,
                             scale: float = 1.0, block_k: int = DEFAULT_BLOCK_K,
                             v_width=None, k_scale=None, v_scale=None,
                             interpret: bool = False):
    """q: (B, KVH, T, G, hdq); k_chunk/v_chunk: (B, T, KVH, hdq/hdv);
    k_cache/v_cache: (B, C, KVH, hdq/hdv); offs: (B,) int32 chunk start
    positions.  Returns (B, KVH, T, G, hdv) in q.dtype.  ``v_width``:
    read only the first lanes of both v operands (which may alias their
    k counterparts — the MLA concatenated latent cache).
    ``k_scale``/``v_scale``: (B, C, KVH) float32 per-row scales when the
    cache holds quantized codes (chunk k/v stay full precision)."""
    b, kvh, t, g, hdq = q.shape
    c = k_cache.shape[1]
    hdv = v_width if v_width is not None else v_cache.shape[-1]
    bk_c = pick_block_k(c, block_k)
    bk_t = pick_block_k(t, block_k)
    if not interpret:
        check_block(bk_c, c, "prefill_attention cache blocks")
        check_block(bk_t, t, "prefill_attention chunk blocks")
    cache_steps = c // bk_c
    chunk_steps = t // bk_t
    total_steps = cache_steps + chunk_steps
    quantized = k_scale is not None
    if quantized and v_scale is None:
        v_scale = k_scale

    def q_map(bi, ki, offs):
        return (bi, 0, 0, 0, 0)

    def _block(bi, ki, offs):
        # Clamp beyond-prefix blocks (and the whole chunk phase) to the
        # row's last needed cache block: a revisited block index elides
        # the HBM->VMEM copy entirely.
        last = jnp.minimum(jnp.maximum(offs[bi] - 1, 0), c - 1) // bk_c
        return jnp.minimum(ki, last)

    def cache_map(bi, ki, offs):
        return (bi, _block(bi, ki, offs), 0, 0)

    def scale_map(bi, ki, offs):
        # Code block and scale block share the clamp: both DMAs elide.
        return (bi, _block(bi, ki, offs), 0)

    def chunk_map(bi, ki, offs):
        # Parked at block 0 during the cache phase (no copy after the
        # first revisit), then walks the chunk.
        j = jnp.clip(ki - cache_steps, 0, chunk_steps - 1)
        return (bi, j, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kvh, t, g, hdq), q_map),
        pl.BlockSpec((1, bk_t, kvh, hdq), chunk_map),
        pl.BlockSpec((1, bk_t, kvh, hdv), chunk_map),
        pl.BlockSpec((1, bk_c, kvh, hdq), cache_map),
        pl.BlockSpec((1, bk_c, kvh, hdv), cache_map),
    ]
    operands = [q, k_chunk, v_chunk, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk_c, kvh), scale_map),
                     pl.BlockSpec((1, bk_c, kvh), scale_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _prefill_kernel, scale=scale, ring=ring, window=window,
        softcap=softcap, bk_c=bk_c, bk_t=bk_t, cache_steps=cache_steps,
        total_steps=total_steps, cache_size=c, chunk=t, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, total_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, t, g, hdv), q_map),
        scratch_shapes=scratch((kvh, t, g), hdv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, t, g, hdv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="prefill_attention",
    )(offs.astype(jnp.int32), *operands)
