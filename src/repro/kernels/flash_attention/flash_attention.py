"""Flash attention (blockwise online softmax) for TPU.

Grid (B*H, Sq/bq, Skv/bk), kv innermost with *arbitrary* semantics: the
(bq, hd) fp32 accumulator plus the running row-max m and row-sum l live in
VMEM scratch across the kv sweep; each step loads one (bk, hd) K/V block,
computes (bq, bk) scores on the MXU, applies causal/window masking and
optional logit soft-capping, and folds the block into (m, l, acc) with the
standard rescaling.  The final kv step writes acc / l.

GQA without materializing repeated K/V: K and V keep their (B*KVH, S, hd)
layout and the BlockSpec index map sends query-head h to kv-head
h // (H // KVH) — the repeat happens in the index map, not in HBM.

Fully-masked blocks above the causal diagonal (and outside the sliding
window) are skipped entirely: the mask bounds are block-static, so the
kernel issues no MXU work for them (the flash trick that halves causal
FLOPs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constants import NEG_INF


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window, softcap,
                  bq: int, bk: int, kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq
    k_lo = ki * bk
    # block-level skip: entirely above the diagonal / outside the window
    needed = True
    if causal:
        needed = k_lo <= q_lo + bq - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, k_lo + bk - 1 >= q_lo - (window - 1)) \
            if causal else needed

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= rows >= cols
        if window is not None:
            mask &= rows - cols < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                  # (bk, hd)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if isinstance(needed, bool):
        if needed:
            compute()
    else:
        jax.lax.cond(needed, compute, lambda: None)

    @pl.when(ki == kv_steps - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window=None, softcap=None, scale: float = 1.0,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool = False):
    """q: (BH, Sq, hd), k/v: (BKVH, Skv, hd); BH % BKVH == 0."""
    bh, sq, hd = q.shape
    bkvh, skv, _ = k.shape
    group = bh // bkvh
    bq, bk = min(block_q, sq), min(block_k, skv)
    grid = (bh, sq // bq, skv // bk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, kv_steps=grid[2])

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # m: running row max
            pltpu.VMEM((bq, 1), jnp.float32),    # l: running row sum
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
