"""Tiled GEMM — the paper's Polybench MXU probe, TPU-blocked.

Grid (M/bm, N/bn, K/bk) with the K axis innermost and *arbitrary*
(sequential) semantics: each (i, j) output tile stays resident in VMEM
as an fp32 accumulator across the K sweep, (bm, bk) x (bk, bn) input
tiles stream through VMEM, and the MXU sees 128-aligned matmuls with
``preferred_element_type=float32`` (bf16 in, fp32 accumulate — the TPU
equivalent of the CUDA tensor-core epilogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(a_ref, b_ref, o_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32)


def gemm_pallas(a, b, block_m: int = 256, block_n: int = 256,
                block_k: int = 256, interpret: bool = False):
    """a: (M, K), b: (K, N) -> fp32 (M, N). Dims multiples of blocks."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
