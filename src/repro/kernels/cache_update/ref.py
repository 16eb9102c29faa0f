"""Pure-jax oracle for the per-row cache scatter.

One ``dynamic_update_slice`` per batch row under ``vmap`` — exactly the
semantics the Pallas kernel must reproduce (and the serve engine's
fallback path where Pallas is unavailable, e.g. CPU/GPU backends).

The ``quant_*`` twins quantize with :func:`repro.kernels.quant.quantize`
— per-row elementwise ops, so quantizing the whole chunk here and one
row per program in the kernel produces bit-identical codes and scales.
"""
import jax
import jax.numpy as jnp

from repro.kernels import quant


def cache_update_ref(cache: jnp.ndarray, new: jnp.ndarray,
                     slots: jnp.ndarray) -> jnp.ndarray:
    """cache: (B, C, *rest)  new: (B, 1, *rest)  slots: (B,) int32."""

    def row(c, n, s):
        starts = (s,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), starts)

    return jax.vmap(row)(cache, new, slots.astype(jnp.int32))


def paged_cache_update_ref(pool: jnp.ndarray, layer, new: jnp.ndarray,
                           page_table: jnp.ndarray, starts: jnp.ndarray,
                           valids: jnp.ndarray) -> jnp.ndarray:
    """Paged-scatter oracle: one flat scatter into the stacked pool.

    pool: (L, P, page_size, *rest)  layer: int32 scalar in [0, L)
    new: (B, T, *rest)  page_table: (B, NB) int32   starts/valids: (B,).

    Row ``t`` of ``new[b]`` lands at physical row
    ``page_table[b, (starts[b]+t) // ps] * ps + (starts[b]+t) % ps`` of
    layer ``layer``'s flattened pages when ``t < valids[b]``; masked
    rows are routed to that layer's scratch page 0 (row 0), whose
    content is undefined by contract — parity tests compare pools
    *excluding* page 0.  The scatter addresses the whole stack, so no
    layer is sliced out.
    """
    n, p, ps = pool.shape[:3]
    b, t = new.shape[:2]
    nb = page_table.shape[1]
    pos = starts.astype(jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)
    pos = jnp.minimum(pos, nb * ps - 1)                     # (B, T)
    ok = jnp.arange(t, dtype=jnp.int32)[None, :] < \
        valids.astype(jnp.int32)[:, None]
    page = jnp.where(ok, jnp.take_along_axis(
        page_table.astype(jnp.int32), pos // ps, axis=1), 0)
    row = jnp.where(ok, pos % ps, 0)
    page = page + jnp.asarray(layer, jnp.int32) * p
    flat = pool.reshape(n * p * ps, -1)
    out = flat.at[(page * ps + row).reshape(-1)].set(
        new.reshape(b * t, -1).astype(pool.dtype))
    return out.reshape(pool.shape)


def quant_cache_update_ref(cache: jnp.ndarray, scales: jnp.ndarray,
                           new: jnp.ndarray, slots: jnp.ndarray, mode: str):
    """Quantizing twin: quantize ``new`` per row, scatter codes into
    ``cache`` and scales into ``scales``.

    cache: (B, C, *rest) codes  scales: (B, C, *rest[:-1]) float32
    new: (B, 1, *rest) full precision  slots: (B,) int32.
    """
    codes, s = quant.quantize(new, mode)
    return (cache_update_ref(cache, codes, slots),
            cache_update_ref(scales, s, slots))


def quant_paged_cache_update_ref(pool: jnp.ndarray, scales: jnp.ndarray,
                                 layer, new: jnp.ndarray,
                                 page_table: jnp.ndarray,
                                 starts: jnp.ndarray, valids: jnp.ndarray,
                                 mode: str):
    """Paged quantizing twin: codes land in ``pool``, scales in the
    page-aligned ``scales`` pool (same page-id space, same masking).

    pool: (L, P, page_size, *rest)  scales: (L, P, page_size, *rest[:-1])
    layer: int32 scalar   new: (B, T, *rest)  page_table: (B, NB)
    starts/valids: (B,) int32.
    """
    codes, s = quant.quantize(new, mode)
    return (paged_cache_update_ref(pool, layer, codes, page_table, starts,
                                   valids),
            paged_cache_update_ref(scales, layer, s, page_table, starts,
                                   valids))
