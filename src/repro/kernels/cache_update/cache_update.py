"""Per-row KV-cache scatter — the continuous-batching cache kernel.

Sequence-level continuous batching gives every batch slot its own
position counter, so one decode step writes row ``b``'s new key/value at
``slots[b]`` — a *different* cache offset per row.  XLA's
``dynamic_update_slice`` only takes one start index per axis, so the
stock lowering is a batch of B separate single-row updates (or a one-hot
scatter that touches the whole cache).  This kernel does the write as a
true scatter: the grid walks the batch, the output BlockSpec's index map
reads the slot from scalar-prefetch SMEM, and each program writes its
row in place.  The cache operand is aliased to the output, so untouched
rows are never copied.

Two block forms, chosen from the cache's rank, because the TPU tiles
the last two dims of an array by (8, 128) and a block must either fill
a tile or span the whole dim:

  * **row blocks** — caches with two or more trailing dims per slot
    (attention K/V ``(B, C, KVH, hd)``, quantized codes): the slot axis
    is a major dim, so a ``(1, 1, KVH, hd)`` block is legal and the
    program writes exactly one row.
  * **aligned read-modify-write blocks** — caches with one trailing dim
    (per-row scales ``(B, C, KVH)``, the MLA latent ``(B, C, r+rope)``):
    the slot axis is the sublane dim, so the block covers ``R`` slots
    (8, or the whole axis when 8 does not divide it), is read in
    through the aliased input, and only the target row is replaced.

The paged writes take the whole stacked pool ``(L, P, page_size,
*rest)`` of a layer stack and the layer to write.  The stack is seen as
one pool of ``L * P`` pages (a free reshape), and the layer rides in
through scalar prefetch into the index maps, which offset the page by
``layer * P``: the whole stack is aliased from input to output, and a
write touches only its new rows, never a layer-sized slice of the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant


def _rows_per_block(shape) -> int:
    """Slots per block for a cache ``shape`` = (N, slots, *rest)."""
    n = shape[1]
    if len(shape) >= 4:
        return 1
    return 8 if n % 8 == 0 else n


def _put(out_ref, base_ref, row, r, first=True):
    """Land ``row`` (the slot's values, shaped like one slot of the
    block) at slot ``r`` of the out block.  Row blocks are overwritten;
    aligned blocks start from the cache's current rows (``base_ref``) on
    the first visit and keep earlier writes on consecutive revisits."""
    rows = out_ref.shape[1]
    if rows == 1:
        out_ref[0, 0] = row.astype(out_ref.dtype)
        return

    if first is True:
        out_ref[...] = base_ref[...]
    else:
        @pl.when(first)
        def _():
            out_ref[...] = base_ref[...]

    ids = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 0)
    out_ref[0] = jnp.where(ids == r, row[None].astype(out_ref.dtype),
                           out_ref[0])


def _cache_spec(shape, index):
    """BlockSpec over a cache ``shape`` whose slot block is picked by
    ``index(*grid, *scalars) -> (lead, slot)``.  Also returns the block
    rows, and the spec under which the aliased cache is passed in (read
    by aligned blocks, untouched by row blocks)."""
    rows = _rows_per_block(shape)
    tail = (0,) * (len(shape) - 2)

    def index_map(*args):
        lead, slot = index(*args)
        return (lead, slot // rows) + tail

    spec = pl.BlockSpec((1, rows) + tuple(shape[2:]), index_map)
    in_spec = spec if rows > 1 else pl.BlockSpec(memory_space=pl.ANY)
    return spec, in_spec, rows


def _scatter_kernel(slots_ref, new_ref, cache_ref, out_ref, *, rows):
    i = pl.program_id(0)
    _put(out_ref, cache_ref, new_ref[0, 0], slots_ref[i] % rows)


def cache_update_pallas(cache: jnp.ndarray, new: jnp.ndarray,
                        slots: jnp.ndarray,
                        interpret: bool = False) -> jnp.ndarray:
    """Scatter ``new[b, 0]`` into ``cache[b, slots[b]]`` for every row.

    cache: (B, C, *rest)   new: (B, 1, *rest)   slots: (B,) int32 in
    [0, C).  Returns the updated cache; the input buffer is aliased.
    """
    b = cache.shape[0]
    lanes = (0,) * (cache.ndim - 2)
    spec, in_spec, rows = _cache_spec(cache.shape,
                                      lambda i, slots: (i, slots[i]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1) + cache.shape[2:],
                         lambda i, slots: (i, 0) + lanes),     # new row
            in_spec,                                           # cache
        ],
        out_specs=spec,
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # index 2 counts the scalar-prefetch operand: (slots, new, cache)
        input_output_aliases={2: 0},
        interpret=interpret,
        name="cache_update",
    )(slots.astype(jnp.int32), new.astype(cache.dtype), cache)


def _paged_route(pt, starts, valids, bi, ti, *, ps: int, nb: int):
    """(physical page, row) of logical position ``starts[bi] + ti``;
    masked rows (``ti >= valids[bi]``) go to scratch page 0, row 0."""
    pos = jnp.minimum(starts[bi] + ti, nb * ps - 1)
    ok = ti < valids[bi]
    page = jnp.where(ok, pt[bi, pos // ps], 0)
    row = jnp.where(ok, pos % ps, 0)
    return page, row


def _first_visit(pt, starts, valids, *, ps: int, nb: int, rows: int):
    """Whether this grid step's aligned pool block differs from the
    previous step's.  Pages are owned by one slot and a slot's positions
    ascend, so the visits to one block are consecutive: the pipeline
    keeps the out block resident across them, and only the first one
    must read the block from the cache."""
    bi, ti, nt = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    route = functools.partial(_paged_route, pt, starts, valids, ps=ps, nb=nb)
    page, row = route(bi, ti)
    prev_b = jnp.where(ti > 0, bi, bi - 1)
    prev_page, prev_row = route(jnp.maximum(prev_b, 0),
                                jnp.where(ti > 0, ti - 1, nt - 1))
    return ((prev_b < 0) | (page != prev_page)
            | (row // rows != prev_row // rows)), row % rows


def _new_spec(shape):
    """BlockSpec for the (B, T, *rest) rows a paged write takes in.  One
    row per grid step where ``rest`` spans two dims; with one trailing
    dim, T sits in the sublane dim and the block is a row's whole chunk
    (revisited, so fetched once per batch row)."""
    lanes = (0,) * (len(shape) - 2)
    if len(shape) >= 4 or shape[1] == 1:
        return pl.BlockSpec((1, 1) + tuple(shape[2:]),
                            lambda bi, ti, *_: (bi, ti) + lanes)
    return pl.BlockSpec(tuple((1,) + shape[1:]),
                        lambda bi, ti, *_: (bi, 0) + lanes)


def _new_row(new_ref):
    """This grid step's row of the block :func:`_new_spec` selected.  A
    whole-chunk block is reduced with a one-hot mask: a packed (bf16)
    block cannot be indexed at an unaligned dynamic sublane, and adding
    zeros in float32 returns the row exactly."""
    if new_ref.shape[1] == 1:
        return new_ref[0, 0]
    blk = new_ref[0].astype(jnp.float32)
    ids = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
    return jnp.sum(jnp.where(ids == pl.program_id(1), blk, 0.0), axis=0)


def _paged_scatter_kernel(pt_ref, starts_ref, valids_ref, layer_ref, new_ref,
                          pool_ref, out_ref, *, ps: int, nb: int, rows: int):
    del layer_ref                                  # read by the index maps
    # The out BlockSpec's index map already routed this program's row
    # (or the scratch page, for masked rows) — see paged_cache_update_pallas.
    first, r = _first_visit(pt_ref, starts_ref, valids_ref, ps=ps, nb=nb,
                            rows=rows)
    _put(out_ref, pool_ref, _new_row(new_ref), r, first)


def _flat(pool: jnp.ndarray) -> jnp.ndarray:
    """A stacked (L, P, page_size, *rest) pool as one (L * P, ...) pool."""
    return pool.reshape((-1,) + pool.shape[2:])


def _paged_pool_spec(shape, *, nb: int):
    """Out spec, aliased-input spec and block rows for one page row of
    the stacked pool ``shape`` = (L, P, page_size, *rest), passed in as
    :func:`_flat` of it and routed by the index maps of a paged write:
    scalar prefetch is (page_table, starts, valids, layer), and the
    layer offsets the page by ``layer * P``."""
    n, p, ps = shape[:3]
    route = functools.partial(_paged_route, ps=ps, nb=nb)

    def index(bi, ti, pt, starts, valids, layer):
        page, row = route(pt, starts, valids, bi, ti)
        return layer[0] * p + page, row

    return _cache_spec((n * p,) + tuple(shape[2:]), index)


def paged_cache_update_pallas(pool: jnp.ndarray, layer, new: jnp.ndarray,
                              page_table: jnp.ndarray, starts: jnp.ndarray,
                              valids: jnp.ndarray,
                              interpret: bool = False) -> jnp.ndarray:
    """Paged scatter: row ``t`` of ``new[b]`` lands at logical position
    ``starts[b] + t`` of row ``b``'s paged cache in layer ``layer``.

    pool: (L, P, page_size, *rest) the stack's physical pages, shared
    by all rows.  layer: int32 scalar (may be traced) in [0, L).
    new: (B, T, *rest) rows to write.  page_table: (B, NB) int32 logical
    block -> physical page.  starts: (B,) int32 first logical position.
    valids: (B,) int32 — rows ``t >= valids[b]`` are masked: the index
    map routes them to the scratch page 0 (whose content is undefined
    by contract) so pad rows never touch real pages.

    The same kernel covers both paged write paths: decode (T == 1,
    valids == 1) and chunked prefill (T == chunk, per-row valid
    lengths).  Returns the updated pool; the whole stacked input pool is
    aliased, and only the written rows change.
    """
    ps = pool.shape[2]
    b, t = new.shape[:2]
    nb = page_table.shape[1]
    spec, in_spec, rows = _paged_pool_spec(pool.shape, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, t),
        in_specs=[_new_spec(new.shape), in_spec],              # new, pool
        out_specs=spec,
    )
    return pl.pallas_call(
        functools.partial(_paged_scatter_kernel, ps=ps, nb=nb, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(_flat(pool).shape, pool.dtype),
        # index 5 counts the scalar-prefetch operands:
        # (page_table, starts, valids, layer, new, pool)
        input_output_aliases={5: 0},
        interpret=interpret,
        name="paged_cache_update",
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      valids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      new.astype(pool.dtype), _flat(pool)).reshape(pool.shape)


# -- fused quantize + scatter (quantized KV caches) ---------------------------
#
# The quantized cache stores low-bit codes plus one float32 absmax
# scale per (token, head) row (kernels/quant.py).  These twins fuse the
# quantization into the scatter: each program reads its full-precision
# row, computes the per-head absmax scale in-register, and writes the
# codes row (a row block) and the scale row (an aligned block of the
# (N, slots, H) scale array) into their aliased caches — so a decode
# step's cache write streams the incoming row once, at full precision,
# and everything it stores is already quantized.

def _quantize_row(x, mode: str):
    """(H, D) full-precision row -> (codes (H, D), scales (H,)), with
    the op order of ``quant.quantize``."""
    qm = quant.qmax(mode)
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    s = jnp.maximum(amax, quant.SCALE_EPS) * quant.qmax_inv(mode)
    y = x / s[:, None]
    if mode == "int8":
        y = jnp.round(y)
    return jnp.clip(y, -qm, qm), s


def _quant_scatter_kernel(slots_ref, new_ref, cache_ref, scales_ref,
                          out_ref, s_out_ref, *, mode, rows):
    del cache_ref                                  # aliased, never read
    i = pl.program_id(0)
    codes, s = _quantize_row(new_ref[0, 0], mode)
    _put(out_ref, None, codes, 0)
    _put(s_out_ref, scales_ref, s, slots_ref[i] % rows)


def quant_cache_update_pallas(cache: jnp.ndarray, scales: jnp.ndarray,
                              new: jnp.ndarray, slots: jnp.ndarray,
                              mode: str,
                              interpret: bool = False):
    """Quantize ``new[b, 0]`` per head row and scatter codes + scales at
    ``slots[b]``.

    cache: (B, C, H, D) codes   scales: (B, C, H) float32
    new: (B, 1, H, D) full precision   slots: (B,) int32 in [0, C).
    Returns (cache, scales) updated; both input buffers are aliased.
    """
    b, _, h, d = cache.shape
    index = lambda i, slots: (i, slots[i])
    spec, _, _ = _cache_spec(cache.shape, index)
    s_spec, s_in_spec, rows = _cache_spec(scales.shape, index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda i, slots: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),                # cache
            s_in_spec,                                        # scales
        ],
        out_specs=[spec, s_spec],
    )
    return pl.pallas_call(
        functools.partial(_quant_scatter_kernel, mode=mode, rows=rows),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(cache.shape, cache.dtype),
                   jax.ShapeDtypeStruct(scales.shape, scales.dtype)],
        # operands: (slots, new, cache, scales)
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        name="quant_cache_update",
    )(slots.astype(jnp.int32), new, cache, scales)


def _quant_paged_scatter_kernel(pt_ref, starts_ref, valids_ref, layer_ref,
                                new_ref, pool_ref, spool_ref, out_ref,
                                s_out_ref, *, mode, ps: int, nb: int,
                                rows: int):
    del layer_ref, pool_ref         # read by the index maps; never read
    codes, s = _quantize_row(new_ref[0, 0], mode)
    _put(out_ref, None, codes, 0)
    first, r = _first_visit(pt_ref, starts_ref, valids_ref, ps=ps, nb=nb,
                            rows=rows)
    _put(s_out_ref, spool_ref, s, r, first)


def quant_paged_cache_update_pallas(pool: jnp.ndarray, scales: jnp.ndarray,
                                    layer, new: jnp.ndarray,
                                    page_table: jnp.ndarray,
                                    starts: jnp.ndarray, valids: jnp.ndarray,
                                    mode: str,
                                    interpret: bool = False):
    """Paged twin of :func:`quant_cache_update_pallas`: quantize row
    ``t`` of ``new[b]`` and land codes + scale at logical position
    ``starts[b] + t`` through the page table (masked rows -> scratch
    page 0, same contract as ``paged_cache_update_pallas`` — the scale
    pool pages alongside its code pool, so the per-row scales are
    page-granular and travel with the page through prefix sharing).

    pool: (L, P, page_size, H, D) codes
    scales: (L, P, page_size, H) f32   layer: int32 scalar in [0, L)
    new: (B, T, H, D)   page_table: (B, NB) int32   starts/valids: (B,).
    Returns (pool, scales) updated; both whole stacked input buffers
    are aliased.
    """
    ps, h, d = pool.shape[2:]
    b, t = new.shape[:2]
    nb = page_table.shape[1]
    spec, _, _ = _paged_pool_spec(pool.shape, nb=nb)
    s_spec, s_in_spec, rows = _paged_pool_spec(scales.shape, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec((1, 1, h, d), lambda bi, ti, *_:
                         (bi, ti, 0, 0)),                     # new row
            pl.BlockSpec(memory_space=pl.ANY),                # pool
            s_in_spec,                                        # scale pool
        ],
        out_specs=[spec, s_spec],
    )
    out, s_out = pl.pallas_call(
        functools.partial(_quant_paged_scatter_kernel, mode=mode, ps=ps,
                          nb=nb, rows=rows),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(_flat(pool).shape, pool.dtype),
                   jax.ShapeDtypeStruct(_flat(scales).shape, scales.dtype)],
        # operands: (page_table, starts, valids, layer, new, pool, scales)
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        name="quant_paged_cache_update",
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      valids.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      new, _flat(pool), _flat(scales))
    return out.reshape(pool.shape), s_out.reshape(scales.shape)
