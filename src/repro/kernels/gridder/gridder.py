"""GRIDDER / DEGRIDDER — image-domain-gridding kernels (paper ref. [2]).

The computational core of IDG: every visibility v with baseline
coordinates (u, v) contributes ``vis_v * exp(2 pi i (u x_p + v y_p))`` to
every pixel p of a subgrid (gridder); the degridder is the adjoint
(predict visibilities from a subgrid).

TPU adaptation (DESIGN.md §4): the CUDA original assigns one thread per
pixel and loops visibilities in registers.  Here the pixel axis is the
MXU row dim: per (subgrid, vis-block) grid step, the phase matrix
(P, bv) = lm (P, 2) @ uv (2, bv) is built by one small matmul, sin/cos on
the VPU, and the accumulation Σ_v phasor_v vis_v is two (P, bv) @ (bv, 2)
MXU matmuls into an fp32 VMEM accumulator that stays resident across the
visibility sweep (same K-accumulation idiom as gemm).  Complex numbers
are real/imag planes — TPUs have no complex MXU type.

Shapes: lm (P, 2) pixel coords; uv (S, V, 2); vis (S, V, 2) re/im.
Out: subgrids (S, P, 2).  P and V multiples of 128 (pad outside).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TWO_PI = 2.0 * math.pi


def _gridder_kernel(lm_ref, uv_ref, vis_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lm = lm_ref[...]                    # (P, 2)
    uv = uv_ref[0]                      # (bv, 2)
    vis = vis_ref[0]                    # (bv, 2) re/im
    phase = TWO_PI * jnp.dot(lm, uv.T, preferred_element_type=jnp.float32)
    c, s = jnp.cos(phase), jnp.sin(phase)           # (P, bv)
    vr, vi = vis[:, 0], vis[:, 1]
    # (vr + i vi) * (c + i s) summed over v
    re = jnp.dot(c, vr[:, None], preferred_element_type=jnp.float32) \
        - jnp.dot(s, vi[:, None], preferred_element_type=jnp.float32)
    im = jnp.dot(s, vr[:, None], preferred_element_type=jnp.float32) \
        + jnp.dot(c, vi[:, None], preferred_element_type=jnp.float32)
    o_ref[0] += jnp.concatenate([re, im], axis=1)


def gridder_pallas(lm, uv, vis, block_v: int = 128,
                   interpret: bool = False):
    s, v, _ = uv.shape
    p = lm.shape[0]
    bv = min(block_v, v)
    grid = (s, v // bv)
    return pl.pallas_call(
        _gridder_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, 2), lambda i, k: (0, 0)),
            pl.BlockSpec((1, bv, 2), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, bv, 2), lambda i, k: (i, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, p, 2), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, p, 2), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lm, uv, vis)


def _degridder_kernel(lm_ref, uv_ref, sub_ref, o_ref):
    lm = lm_ref[...]                    # (P, 2)
    uv = uv_ref[0]                      # (bv, 2)
    sub = sub_ref[0]                    # (P, 2)
    phase = TWO_PI * jnp.dot(uv, lm.T, preferred_element_type=jnp.float32)
    c, s = jnp.cos(phase), jnp.sin(phase)           # (bv, P)
    gr, gi = sub[:, 0], sub[:, 1]
    # adjoint: conj phasor — vis_v = sum_p (gr + i gi) * (c - i s)
    re = jnp.dot(c, gr[:, None], preferred_element_type=jnp.float32) \
        + jnp.dot(s, gi[:, None], preferred_element_type=jnp.float32)
    im = jnp.dot(c, gi[:, None], preferred_element_type=jnp.float32) \
        - jnp.dot(s, gr[:, None], preferred_element_type=jnp.float32)
    o_ref[0] = jnp.concatenate([re, im], axis=1)


def degridder_pallas(lm, uv, subgrids, block_v: int = 128,
                     interpret: bool = False):
    s, v, _ = uv.shape
    p = lm.shape[0]
    bv = min(block_v, v)
    grid = (s, v // bv)
    return pl.pallas_call(
        _degridder_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, 2), lambda i, k: (0, 0)),
            pl.BlockSpec((1, bv, 2), lambda i, k: (i, k, 0)),
            pl.BlockSpec((1, p, 2), lambda i, k: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bv, 2), lambda i, k: (i, k, 0)),
        out_shape=jax.ShapeDtypeStruct((s, v, 2), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lm, uv, subgrids)
