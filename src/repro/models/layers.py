"""Shared layers: norms, MLPs, embeddings, RoPE (incl. M-RoPE), softcap."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnConfig
from repro.sharding.specs import annotate, shard


# -- init helpers ------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches common LM practice)."""
    fan_in = shape[in_axis] if isinstance(in_axis, int) else \
        math.prod(shape[a] for a in in_axis)
    std = 1.0 / math.sqrt(max(1, fan_in))
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * std


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# -- norms ---------------------------------------------------------------------

def init_norm(key, cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm_nonparam":
        return {}  # olmo: non-parametric LN has no weights
    p = {"scale": annotate(jnp.ones((d,), jnp.float32), "d_model")}
    if cfg.norm_type == "layernorm":  # whisper: parametric LN with bias
        p["bias"] = annotate(jnp.zeros((d,), jnp.float32), "d_model")
    return p


def apply_norm(cfg: ModelConfig, p, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm_type in ("layernorm_nonparam", "layernorm"):
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        if cfg.norm_bf16_io:
            # bf16 datapath: only the (B,S,1) stats stay fp32, so the
            # upstream TP all-reduce keeps a bf16 operand (§Perf)
            y = (x - mu.astype(dtype)) * jax.lax.rsqrt(
                var + eps).astype(dtype)
            if p:
                y = y * p["scale"].astype(dtype)
                if "bias" in p:
                    y = y + p["bias"].astype(dtype)
            return y
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
        if p:
            y = y * p["scale"].astype(jnp.float32)
            if "bias" in p:
                y = y + p["bias"].astype(jnp.float32)
        return y.astype(dtype)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    if cfg.norm_bf16_io:
        y = x * jax.lax.rsqrt(ms + eps).astype(dtype)
        if p:
            y = y * p["scale"].astype(dtype)
        return y
    y = x32 * jax.lax.rsqrt(ms + eps)
    if p:
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(dtype)


# -- softcap (gemma2) ------------------------------------------------------------

def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# -- MLP -----------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d: Optional[int] = None,
             ff: Optional[int] = None):
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": annotate(dense_init(k1, (d, ff)), "d_model", "ffn"),
         "w_down": annotate(dense_init(k2, (ff, d), in_axis=0), "ffn",
                            "d_model")}
    if cfg.act in ("silu", "geglu"):  # gated (SwiGLU / GeGLU)
        p["w_gate"] = annotate(dense_init(k3, (d, ff)), "d_model", "ffn")
    return p


def apply_mlp(cfg: ModelConfig, p, x):
    dt = x.dtype
    up = x @ p["w_up"].astype(dt)
    up = shard(up, "batch", "seq", "ffn")
    if cfg.act in ("silu", "geglu"):
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        up = act(x @ p["w_gate"].astype(dt)) * up
    else:
        up = jax.nn.gelu(up)
    out = up @ p["w_down"].astype(dt)
    return shard(out, "batch", "seq", "d_model")


# -- embedding / head -------------------------------------------------------------

def init_embedding(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {"embedding": annotate(embed_init(k1, (cfg.vocab_size, cfg.d_model)),
                               "vocab", "d_model")}
    if not cfg.tie_embeddings:
        p["lm_head"] = annotate(dense_init(k2, (cfg.d_model, cfg.vocab_size)),
                                "d_model", "vocab")
    return p


def embed_tokens(cfg: ModelConfig, p, tokens):
    emb = p["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:  # gemma-style sqrt(d) embedding scale
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return shard(x, "batch", "seq", "d_model")


def logits_from_hidden(cfg: ModelConfig, p, x):
    if cfg.tie_embeddings:
        w = p["embedding"].astype(x.dtype)
        logits = x @ w.T
    else:
        logits = x @ p["lm_head"].astype(x.dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return shard(logits, "batch", "seq", "vocab")


# -- RoPE ----------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float,
                     yarn: Optional[YarnConfig] = None) -> jnp.ndarray:
    """Inverse frequencies of the ``head_dim // 2`` rotated pairs.  With
    ``yarn``: YaRN's blend (DeepSeek-V3's ``precompute_freqs_cis``) of
    the frequencies divided by ``factor`` (interpolated) and the
    original ones (extrapolated), by a linear ramp between the dims
    that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings`` positions."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return inv

    def corr_dim(rotations):
        return head_dim * math.log(
            yarn.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def yarn_softmax_factor(yarn: Optional[YarnConfig]) -> float:
    """YaRN's attention-scale factor ``mscale**2``, with ``mscale =
    0.1 * mscale_all_dim * ln(factor) + 1`` (1 without YaRN, or when
    ``mscale_all_dim`` is 0)."""
    if yarn is None or not yarn.mscale_all_dim or yarn.factor <= 1:
        return 1.0
    m = 0.1 * yarn.mscale_all_dim * math.log(yarn.factor) + 1.0
    return m * m


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               m_rope_sections: Optional[Tuple[int, int, int]] = None,
               yarn: Optional[YarnConfig] = None):
    """Rotary embedding.

    x: (B, S, H, hd). positions: (B, S) for standard RoPE, or (B, S, 3)
    for M-RoPE (qwen2-vl), where the half-dim is split into
    ``m_rope_sections`` chunks driven by the temporal/height/width
    position streams respectively.  ``yarn``: YaRN-scaled frequencies
    (see ``rope_frequencies``).
    """
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta, yarn)    # (half,)
    if m_rope_sections is not None and positions.ndim == 3:
        secs = _scaled_sections(m_rope_sections, hd // 2)
        comp = jnp.concatenate(
            [jnp.full((n,), i, jnp.int32) for i, n in enumerate(secs)])
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),                  # (B,S,3)
            comp[None, None, :].repeat(positions.shape[0], 0)
                .repeat(positions.shape[1], 1), axis=-1)    # (B,S,half)
        angles = pos * inv[None, None, :]
    else:
        angles = positions.astype(jnp.float32)[..., None] * inv  # (B,S,half)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _scaled_sections(sections: Tuple[int, int, int], half: int):
    total = sum(sections)
    scaled = [int(round(s * half / total)) for s in sections]
    scaled[-1] = half - sum(scaled[:-1])
    return scaled


def sinusoidal_embedding(seq: int, d: int, dtype=jnp.float32):
    """Whisper-encoder style fixed sinusoidal positional embedding (S, d)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(1, half - 1))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)],
                           axis=-1).astype(dtype)


def sinusoidal_row(pos, d: int, dtype=jnp.float32):
    """Row(s) of :func:`sinusoidal_embedding` at traced position(s).

    pos: scalar -> (d,); (B,) vector (per-slot decode positions) -> (B, d).
    """
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(1, half - 1))
    angles = jnp.asarray(pos, jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)],
                           axis=-1).astype(dtype)


def default_positions(batch: int, seq: int, offset=0):
    return jnp.arange(seq, dtype=jnp.int32)[None, :] + offset \
        + jnp.zeros((batch, 1), jnp.int32)


def mrope_positions(batch: int, seq: int, patch_len: int, offset=0):
    """Stub M-RoPE position ids: a (t,h,w) grid for the leading patch
    region (square-ish grid) and shared temporal positions for text."""
    side = max(1, int(math.isqrt(max(1, patch_len))))
    t = jnp.arange(seq, dtype=jnp.int32)
    h = jnp.where(t < patch_len, (t // side) % side, t)
    w = jnp.where(t < patch_len, t % side, t)
    pos = jnp.stack([t, h, w], axis=-1)[None]  # (1, S, 3)
    pos = pos + offset
    return jnp.broadcast_to(pos, (batch, seq, 3))
