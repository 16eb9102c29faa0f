"""Plain reference of a dense decoder (Qwen3 / OLMo style), and its
random weights.

Written from the published descriptions, not from the program: a
pre-norm decoder of ``num_hidden_layers`` blocks, each

    h = x + Wo . attn(rope(qknorm(Wq . norm(x))), rope(qknorm(Wk . norm(x))),
                      Wv . norm(x))
    x' = h + Wd . (silu(Wg . norm(h)) * (Wu . norm(h)))

with causal grouped-query attention (``num_key_value_heads`` groups,
scale ``head_dim ** -0.5``), rotary embedding on the two halves of each
head (``rope_theta``), then a final norm and a head tied to the token
embedding.  ``norm`` is RMSNorm with a learned scale (Qwen3,
``rms_norm_eps``) or a LayerNorm with no scale and no bias (OLMo,
``layer_norm_eps``); ``qk_norm`` is a per-head RMSNorm over ``head_dim``
with a learned scale (Qwen3 only).

Everything is float32, every matmul at ``Precision.HIGHEST``.  The
``fp8`` variant rounds both operands of every matmul to float8_e4m3
(per-tensor scale for weights, per-row for activations) before the
same float32 product: the control that a lower precision must fail.

Weights come from ``make_weights``: a flat dict of stacked arrays drawn
from the seed, stored in bfloat16 as they are served.  Scales keep every
block's output comparable to the residual stream, so that attention and
the MLP of every layer move the logits (see ``make_weights``).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def key_from_seed(seed: int):
    """A PRNG key holding every bit of a non-negative ``seed``."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def shapes(c: Dict) -> Dict[str, tuple]:
    """Shape of each weight of config ``c`` (published key names)."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    ff, v = c["intermediate_size"], c["vocab_size"]
    s = {"embed": (v, d),
         "wq": (L, d, h * hd), "wk": (L, d, kvh * hd),
         "wv": (L, d, kvh * hd), "wo": (L, h * hd, d),
         "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    if c["norm"] == "rmsnorm":
        s.update(attn_norm=(L, d), mlp_norm=(L, d), final_norm=(d,))
    if c["qk_norm"]:
        s.update(q_norm=(L, hd), k_norm=(L, hd))
    return s


def make_weights(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """Random weights in bfloat16.  Projections are normal with std
    ``fan_in ** -0.5``, so each block adds O(1) per element to the
    residual; the embedding has std 0.02 (its rows enter every block
    through a norm, and the tied head then gives logits of std ~0.02 *
    sqrt(hidden)); norm scales are 1 + 0.1 N(0, 1), so that a program
    that skipped one would differ.  Call under ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            w = 0.02 * z
        elif name.endswith("norm"):
            w = 1.0 + 0.1 * z
        else:
            w = z / math.sqrt(shape[-2])
        out[name] = w.astype(jnp.bfloat16)
    return out


def _q8(x, axis):
    """Round to float8_e4m3 under an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """``a @ b`` (activations a: (..., k); weights b: (k, n))."""
    if fp8:
        a = _q8(a, -1)
        b = _q8(b, None)
    return jnp.matmul(a, b, precision=HIGHEST)


def _eps(c):
    return c["rms_norm_eps"] if c["norm"] == "rmsnorm" \
        else c["layer_norm_eps"]


def _norm(c, x, scale):
    if c["norm"] == "rmsnorm":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + _eps(c)) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + _eps(c))


def _rope(x, pos, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits_at(c: Dict, w: Dict, tokens, at, fp8: bool = False):
    """Logits (len(at), vocab) float32 after ``tokens`` (S,), read at
    positions ``at``.  Causal, so padding after the last position read
    changes nothing."""
    f32 = lambda a: a.astype(jnp.float32)
    S = tokens.shape[0]
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = f32(w["embed"])[tokens]
    ones = jnp.ones((c["hidden_size"],), jnp.float32)
    layer_names = [n for n in w if n not in ("embed", "final_norm")]

    def block(x, lw):
        lw = {n: f32(a) for n, a in lw.items()}
        a = _norm(c, x, lw.get("attn_norm", ones))
        q = _mm(a, lw["wq"], fp8).reshape(S, h, hd)
        k = _mm(a, lw["wk"], fp8).reshape(S, kvh, hd)
        v = _mm(a, lw["wv"], fp8).reshape(S, kvh, hd)
        if c["qk_norm"]:
            q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True)
                                  + _eps(c)) * lw["q_norm"]
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                                  + _eps(c)) * lw["k_norm"]
        q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
        k = jnp.repeat(k, h // kvh, axis=1)
        v = jnp.repeat(v, h // kvh, axis=1)
        if fp8:
            q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        if fp8:
            p = _q8(p, -1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _mm(o.reshape(S, h * hd), lw["wo"], fp8)
        m = _norm(c, x, lw.get("mlp_norm", ones))
        g = jax.nn.silu(_mm(m, lw["w_gate"], fp8)) * _mm(m, lw["w_up"], fp8)
        return x + _mm(g, lw["w_down"], fp8), None

    x, _ = jax.lax.scan(block, x, {n: w[n] for n in layer_names})
    x = _norm(c, x[at], f32(w["final_norm"]) if "final_norm" in w else ones)
    return _mm(x, f32(w["embed"]).T, fp8)
