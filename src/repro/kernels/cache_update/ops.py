"""Dispatching wrapper for the per-row cache scatter.

``cache_update`` accepts caches with arbitrary trailing dims —
(B, C, KVH, hd) attention K/V, (B, C, R) MLA latents — in their own
layout (the kernel picks its block form from the rank; see
``cache_update.py``), and routes to the Pallas scatter on TPU or the
``vmap``'d ``dynamic_update_slice`` oracle elsewhere.

``impl`` — "auto" (Pallas iff the default backend is TPU), "pallas",
"pallas_interpret" (CPU parity testing), or "lax".  The env var
``PMT_CACHE_UPDATE_IMPL`` overrides "auto" for experiments.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels.cache_update.cache_update import (
    cache_update_pallas, paged_cache_update_pallas,
    quant_cache_update_pallas, quant_paged_cache_update_pallas)
from repro.kernels.cache_update.ref import (cache_update_ref,
                                            paged_cache_update_ref,
                                            quant_cache_update_ref,
                                            quant_paged_cache_update_ref)


def _resolve(impl: str) -> str:
    if impl == "auto":
        impl = os.environ.get("PMT_CACHE_UPDATE_IMPL", "auto")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "lax"
    return impl


def cache_update(cache: jnp.ndarray, new: jnp.ndarray, slots: jnp.ndarray,
                 impl: str = "auto") -> jnp.ndarray:
    """Write ``new[b, 0]`` at ``cache[b, slots[b]]`` for every batch row.

    cache: (B, C, *rest)   new: (B, 1, *rest)   slots: (B,) int32.
    """
    impl = _resolve(impl)
    if impl == "lax":
        return cache_update_ref(cache, new, slots)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown cache_update impl {impl!r}")
    return cache_update_pallas(cache, new, slots,
                               interpret=impl == "pallas_interpret")


def paged_cache_update(pool: jnp.ndarray, layer, new: jnp.ndarray,
                       page_table: jnp.ndarray, starts: jnp.ndarray,
                       valids: jnp.ndarray, impl: str = "auto") -> jnp.ndarray:
    """Write ``new[b, t]`` at logical position ``starts[b] + t`` of row
    ``b``'s paged cache in layer ``layer``, for ``t < valids[b]``
    (masked rows land in the scratch page 0, whose content is
    undefined).

    pool: (L, P, page_size, *rest) a layer stack's physical pages,
    shared by all rows (one unstacked pool is ``pool[None]``, layer 0).
    layer: int32 scalar, may be traced.
    new: (B, T, *rest)   page_table: (B, NB) int32   starts/valids: (B,).
    One call covers both paged write paths: decode (T == 1) and chunked
    prefill (T == chunk).  Dispatches on ``PMT_CACHE_UPDATE_IMPL`` like
    ``cache_update``.
    """
    impl = _resolve(impl)
    if impl == "lax":
        return paged_cache_update_ref(pool, layer, new, page_table, starts,
                                      valids)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown cache_update impl {impl!r}")
    return paged_cache_update_pallas(pool, layer, new, page_table, starts,
                                     valids,
                                     interpret=impl == "pallas_interpret")


# -- quantized writes (codes + per-row scales) --------------------------------

def _quant_heads(cache) -> int:
    """Rows per token: product of the dims between position and the
    quantized last axis — KVH for attention K/V, 1 for MLA latents."""
    h = 1
    for n in cache.shape[2:-1]:
        h *= n
    return h


def quant_cache_update(cache: jnp.ndarray, scales: jnp.ndarray,
                       new: jnp.ndarray, slots: jnp.ndarray, mode: str,
                       impl: str = "auto"):
    """Quantize ``new[b, 0]`` (per-row absmax over the last axis, see
    ``kernels/quant``) and write codes + scales at ``cache[b, slots[b]]``
    / ``scales[b, slots[b]]``.

    cache: (B, C, *rest) codes   scales: (B, C, *rest[:-1]) float32
    new: (B, 1, *rest) full precision   slots: (B,) int32.
    Returns ``(cache, scales)``.  The Pallas path fuses the quantization
    into the scatter (one program per row computes its own scale);
    "lax" quantizes the row then runs two oracle scatters — bit-
    identical results either way.
    """
    impl = _resolve(impl)
    if impl == "lax":
        return quant_cache_update_ref(cache, scales, new, slots, mode)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown cache_update impl {impl!r}")
    b, c = cache.shape[:2]
    h, d = _quant_heads(cache), cache.shape[-1]
    out, s_out = quant_cache_update_pallas(
        cache.reshape(b, c, h, d), scales.reshape(b, c, h),
        new.reshape(b, 1, h, d), slots, mode,
        interpret=impl == "pallas_interpret")
    return out.reshape(cache.shape), s_out.reshape(scales.shape)


def quant_paged_cache_update(pool: jnp.ndarray, scales: jnp.ndarray,
                             layer, new: jnp.ndarray,
                             page_table: jnp.ndarray, starts: jnp.ndarray,
                             valids: jnp.ndarray, mode: str,
                             impl: str = "auto"):
    """Paged twin of :func:`quant_cache_update`: codes land in ``pool``
    and scales in the page-aligned ``scales`` pool of layer ``layer``
    through the same page-table indirection (masked rows -> scratch
    page 0 in both).

    pool: (L, P, page_size, *rest)   scales: (L, P, page_size, *rest[:-1])
    layer: int32 scalar   new: (B, T, *rest)   page_table: (B, NB)
    starts/valids: (B,).  Returns ``(pool, scales)``.
    """
    impl = _resolve(impl)
    if impl == "lax":
        return quant_paged_cache_update_ref(pool, scales, layer, new,
                                            page_table, starts, valids, mode)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown cache_update impl {impl!r}")
    n, p, ps = pool.shape[:3]
    b, t = new.shape[:2]
    h, d = _quant_heads(new), pool.shape[-1]
    out, s_out = quant_paged_cache_update_pallas(
        pool.reshape(n, p, ps, h, d), scales.reshape(n, p, ps, h), layer,
        new.reshape(b, t, h, d), page_table, starts, valids, mode,
        interpret=impl == "pallas_interpret")
    return out.reshape(pool.shape), s_out.reshape(scales.shape)
