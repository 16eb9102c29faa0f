"""Flash-decode: length-aware fused decode attention for TPU.

The serve hot path is one new token against a full cache: decode is
memory-bound, so HBM bytes are joules.  Dense decode reads every cache
slot of every row regardless of how many tokens the row actually holds.
This kernel makes the cache read *length-aware*:

  * Grid (B, C/bk), kv blocks innermost with ``arbitrary`` semantics.
    One K/V block spans every kv head, ``(1, bk, KVH, hd)``: its last
    two dims equal the cache's, which is what the TPU's (8, 128) tiling
    rule asks of a block, and the cache layout (B, C, KVH, hd) stays as
    the models, the pager and the swap store write it.  The kernel walks
    the heads of a block in-register; the per-head (G, hdv) fp32
    accumulators plus running row-max m and row-sum l live in VMEM
    scratch across the kv sweep (standard online softmax).
  * The per-row ``cur_len`` vector arrives via scalar prefetch and
    feeds the K/V BlockSpec index maps: blocks entirely beyond a row's
    valid prefix are clamped to the row's last needed block, so the
    pipeline revisits the same index and **never issues their HBM
    reads** — the bandwidth win a dense masked path cannot have.  A
    ``pl.when`` guard skips their MXU work too.
  * GQA is packed, not repeated: all G query heads of one kv head form
    a single (G, hdq) q tile, so each K block feeds one real
    (G, hdq) x (hdq, bk) MXU matmul per kv head instead of G vector
    products, and K/V are read once per block.
  * Sliding-window ring buffers, slot -> position arithmetic, never-
    written-slot validity, and logit soft-capping are handled in-kernel
    from ``cur_len`` alone — no (B, C) position/validity tensors are
    materialised in HBM per decode step.

``v`` may be the same array as ``k`` with ``v_width`` set: the V
BlockSpec then reads only the first ``v_width`` lanes (the MLA latent
cache stores [latent | rope] concatenated; scores use the full row,
values only the latent prefix).

Quantized caches (``k_scale``/``v_scale`` set): k/v hold int8 or
fp8_e4m3 codes and the scale arrays hold one float32 absmax scale per
(slot, kv head) row — see ``kernels/quant``.  The scale blocks
``(1, bk, KVH)`` ride the *same clamped index maps* as their code
blocks (minus the lane axis), so dead blocks elide the scale DMA exactly
like the code DMA, and the kernel dequantizes in-register —
``codes.astype(f32) * scale`` — right before each dot.  The contract
keeps memory traffic at the quantized width: nothing is ever
materialised dequantized in HBM.

Compiled (non-interpret) calls need ``block_k`` to resolve to a multiple
of 8 or to the whole cache: the scale blocks put the cache axis in the
sublane dim, and a block the compiler would refuse is rejected here
with the sizes named.  Interpret mode keeps any divisor (the CPU
reference sweeps use odd sizes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constants import NEG_INF
from repro.kernels.decode_attention.ref import pick_block_k


def check_block(bk: int, size: int, what: str) -> None:
    """Reject a compiled-path block of ``bk`` rows along an axis of
    ``size`` rows that the TPU's sublane tiling cannot take."""
    if bk % 8 and bk != size:
        raise ValueError(
            f"{what}: block of {bk} rows along an axis of {size} is neither "
            f"a multiple of 8 nor the whole axis; compiled Pallas kernels "
            f"need one or the other (pick a block_k / size that 8 divides)")


def unpack(refs, quantized: bool):
    """Split a kernel's trailing refs into (k scale, v scale, o, m, l,
    acc): quantized call sites append two float32 scale operands."""
    if quantized:
        return refs
    return (None, None) + tuple(refs)


def _fold_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
                valid, *, scale: float, softcap):
    """Fold one (bk, KVH, hd) K/V block into every head's online-softmax
    accumulator.  ``valid``: (G, bk) bool, shared by all heads."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32) * scale            # (G, hdq)
        k = k_ref[0, :, h, :].astype(jnp.float32)              # (bk, hdq)
        if ks_ref is not None:
            k = k * ks_ref[0, :, h:h + 1].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # (G, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]                                      # (G, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, :, h, :].astype(jnp.float32)              # (bk, hdv)
        if vs_ref is not None:
            v = v * vs_ref[0, :, h:h + 1].astype(jnp.float32)
        acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def init(m_ref, l_ref, acc_ref):
    """Reset the online-softmax scratch before a kv sweep."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def finish(o_ref, l_ref, acc_ref):
    """Normalise the accumulators into the (1, KVH, ...) out block."""
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, *refs,
                   scale: float, ring: bool, softcap, bk: int,
                   kv_steps: int, cache_size: int,
                   quantized: bool = False):
    ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = unpack(refs, quantized)
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    cur = lens_ref[bi]

    @pl.when(ki == 0)
    def _():
        init(m_ref, l_ref, acc_ref)

    k_lo = ki * bk

    # Blocks whose first slot is past the row's new-token position hold
    # no valid key (full cache: slots > cur unwritten; ring: a not-yet-
    # wrapped tail) — their DMA was elided by the index map, skip the
    # compute as well.
    @pl.when(k_lo <= cur)
    def _compute():
        cols = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], bk), 1)
        if ring:
            # slot s holds position cur - ((cur - s) mod C); valid iff
            # that position is >= 0 (the window mask is subsumed: held
            # positions are within C - 1 <= window - 1 of the query).
            valid = jnp.mod(cur - cols, cache_size) <= cur
        else:
            valid = cols <= cur
        _fold_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                    acc_ref, valid, scale=scale, softcap=softcap)

    @pl.when(ki == kv_steps - 1)
    def _():
        finish(o_ref, l_ref, acc_ref)


def _paged_decode_kernel(lens_ref, pt_ref, q_ref, k_ref, v_ref, *refs,
                         scale: float, window, softcap, ps: int,
                         kv_steps: int, quantized: bool = False):
    ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = unpack(refs, quantized)
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    cur = lens_ref[bi]

    @pl.when(ki == 0)
    def _():
        init(m_ref, l_ref, acc_ref)

    k_lo = ki * ps

    # Paged caches are unwrapped (slot == position): pages beyond the
    # row's new-token position hold nothing, and — for sliding-window
    # layers — pages wholly below ``cur - window + 1`` are all masked.
    # Both ends had their DMA elided by the index-map clamp; skip the
    # compute too.
    live = k_lo <= cur
    if window is not None:
        live &= (k_lo + ps - 1) >= cur - (window - 1)

    @pl.when(live)
    def _compute():
        cols = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], ps), 1)
        valid = cols <= cur
        if window is not None:
            valid &= (cur - cols) < window
        _fold_heads(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                    acc_ref, valid, scale=scale, softcap=softcap)

    @pl.when(ki == kv_steps - 1)
    def _():
        finish(o_ref, l_ref, acc_ref)


def scratch(rows: tuple, hdv: int):
    """VMEM online-softmax state for ``rows`` = (KVH, ...) query rows."""
    return [
        pltpu.VMEM(rows + (1,), jnp.float32),     # m: running row max
        pltpu.VMEM(rows + (1,), jnp.float32),     # l: running row sum
        pltpu.VMEM(rows + (hdv,), jnp.float32),   # acc
    ]


def decode_attention_paged_pallas(q, k_pool, v_pool, layer, page_table, lens,
                                  *, window=None, softcap=None,
                                  scale: float = 1.0, v_width=None,
                                  k_scale=None, v_scale=None,
                                  interpret: bool = False):
    """Paged flash-decode: q (B, KVH, G, hdq) against the stacked
    physical page pools k_pool/v_pool (L, P, page_size, KVH, hd*) of a
    layer stack, at layer ``layer`` (int32 scalar, may be traced),
    through a page_table (B, NB) int32.  lens: (B,) int32 new-token
    positions.  The stack is seen as one pool of ``L * P`` pages (a free
    reshape, so it is read in place, never sliced) through the page
    table offset by ``layer * P``.  One kv block == one physical page
    (all kv heads); the K/V BlockSpec index maps read the page table
    from scalar-prefetch SMEM — the paged lookup is literally "the index
    map reads ``pt[b, block]`` instead of ``(b, block)``", with the
    same clamp-to-elide-DMA trick on both the beyond-``lens`` tail and
    (windowed) the below-window head.
    Returns (B, KVH, G, hdv) in q.dtype.  ``v_width``: read only the
    first lanes of v (``v_pool`` may alias ``k_pool`` — MLA).
    ``k_scale``/``v_scale``: (L, P, page_size, KVH) float32 per-row
    absmax scale pools for quantized code pools; they page through the
    same table and clamp, and the kernel dequantizes in-register."""
    b, kvh, g, hdq = q.shape
    p, ps = k_pool.shape[1:3]
    nb = page_table.shape[1]
    c = nb * ps
    hdv = v_width if v_width is not None else v_pool.shape[-1]
    quantized = k_scale is not None
    if quantized and v_scale is None:
        v_scale = k_scale
    flat = lambda pool: pool.reshape((-1,) + pool.shape[2:])
    page_table = page_table.astype(jnp.int32) + \
        jnp.asarray(layer, jnp.int32) * p

    def q_map(bi, ki, lens, pt):
        return (bi, 0, 0, 0)

    def _page(bi, ki, lens, pt):
        # Clamp the sweep to the row's needed page range, then map the
        # logical page through the page table: a revisited *physical*
        # index elides the HBM->VMEM copy entirely.
        last = jnp.minimum(lens[bi], c - 1) // ps
        j = jnp.minimum(ki, last)
        if window is not None:
            first = jnp.maximum(lens[bi] - (window - 1), 0) // ps
            j = jnp.maximum(j, jnp.minimum(first, last))
        return pt[bi, j]

    def kv_map(bi, ki, lens, pt):
        return (_page(bi, ki, lens, pt), 0, 0, 0)

    def scale_map(bi, ki, lens, pt):
        # Same physical page as the codes: the scale DMA is elided for
        # exactly the pages whose code DMA is elided.
        return (_page(bi, ki, lens, pt), 0, 0)

    in_specs = [
        pl.BlockSpec((1, kvh, g, hdq), q_map),
        pl.BlockSpec((1, ps, kvh, hdq), kv_map),
        pl.BlockSpec((1, ps, kvh, hdv), kv_map),
    ]
    operands = [q, flat(k_pool), flat(v_pool)]
    if quantized:
        in_specs += [pl.BlockSpec((1, ps, kvh), scale_map),
                     pl.BlockSpec((1, ps, kvh), scale_map)]
        operands += [flat(k_scale), flat(v_scale)]

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        ps=ps, kv_steps=nb, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, hdv), q_map),
        scratch_shapes=scratch((kvh, g), hdv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hdv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(lens.astype(jnp.int32), page_table, *operands)


def decode_attention_pallas(q, k, v, lens, *, ring: bool = False,
                            softcap=None, scale: float = 1.0,
                            block_k: int = 128, v_width=None,
                            k_scale=None, v_scale=None,
                            interpret: bool = False):
    """q: (B, KVH, G, hdq), k: (B, C, KVH, hdq), v: (B, C, KVH, hdv),
    lens: (B,) int32 new-token positions.  Returns (B, KVH, G, hdv) in
    q.dtype.  ``v_width``: read only the first lanes of v (see module
    docstring; ``v`` may alias ``k``).  ``k_scale``/``v_scale``:
    (B, C, KVH) float32 per-row absmax scales when k/v hold quantized
    codes; the kernel dequantizes blocks in-register."""
    b, kvh, g, hdq = q.shape
    c = k.shape[1]
    hdv = v_width if v_width is not None else v.shape[-1]
    bk = pick_block_k(c, block_k)
    if not interpret:
        check_block(bk, c, "decode_attention cache blocks")
    kv_steps = c // bk
    quantized = k_scale is not None
    if quantized and v_scale is None:
        v_scale = k_scale

    def q_map(bi, ki, lens):
        return (bi, 0, 0, 0)

    def _block(bi, ki, lens):
        # Clamp beyond-prefix blocks to the row's last needed block: a
        # revisited block index elides the HBM->VMEM copy entirely.
        return jnp.minimum(ki, jnp.minimum(lens[bi], c - 1) // bk)

    def kv_map(bi, ki, lens):
        return (bi, _block(bi, ki, lens), 0, 0)

    def scale_map(bi, ki, lens):
        # Code block and scale block share the clamp: both DMAs elide.
        return (bi, _block(bi, ki, lens), 0)

    in_specs = [
        pl.BlockSpec((1, kvh, g, hdq), q_map),
        pl.BlockSpec((1, bk, kvh, hdq), kv_map),
        pl.BlockSpec((1, bk, kvh, hdv), kv_map),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, bk, kvh), scale_map),
                     pl.BlockSpec((1, bk, kvh), scale_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _decode_kernel, scale=scale, ring=ring, softcap=softcap, bk=bk,
        kv_steps=kv_steps, cache_size=c, quantized=quantized)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, hdv), q_map),
        scratch_shapes=scratch((kvh, g), hdv),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hdv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lens.astype(jnp.int32), *operands)
