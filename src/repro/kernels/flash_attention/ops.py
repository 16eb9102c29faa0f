"""Model-facing flash-attention wrapper.

Accepts the framework's (B, S, H, hd) layout, flattens to the kernel's
(B*H, S, hd), and — so the kernel is usable in training too — wraps the
Pallas forward in jax.custom_vjp with a reference-recompute backward
(flash backward kernels recompute the score blocks; here the recompute is
the jnp oracle, which XLA rematerializes blockwise under the caller's
checkpoint policy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fa(q, k, v, causal, window, softcap, scale, block_q, block_k,
        interpret):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
            interpret):
    out = _fa(q, k, v, causal, window, softcap, scale, block_q, block_k,
              interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, softcap, scale, block_q, block_k, interpret,
            res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, softcap=softcap,
            scale=scale), q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    softcap=None, scale: float = 1.0, block_q: int = 256,
                    block_k: int = 256, interpret=None):
    """q: (B, Sq, H, hd), k/v: (B, Skv, KVH, hd) -> (B, Sq, H, hd).

    ``interpret=None`` compiles the kernel on a TPU backend and runs it
    in interpret mode anywhere else, decided when called."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    of = _fa(qf, kf, vf, causal, window, softcap, scale,
             min(block_q, sq), min(block_k, skv), interpret)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
