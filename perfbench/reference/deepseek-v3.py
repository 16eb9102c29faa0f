"""Plain reference of DeepSeek-V3 (arXiv:2412.19437; the published
config.json and inference code), one chip's share of it, and its random
weights.

A pre-norm decoder: ``first_k_dense_replace`` layers with a dense
SwiGLU MLP, then MoE layers, each

    h  = x + MLA(rmsnorm(x))
    x' = h + FFN(rmsnorm(h))

then a final RMSNorm and an untied head.  Multi-head latent attention,
in the published (non-absorbed) form: per-head keys and values are
materialized from the latent,

    cq     = rmsnorm(x Wq_a) . q_norm;   [q_nope | q_pe] = cq Wq_b
    [c | k_pe] = x Wkv_a;                c = rmsnorm(c) . kv_norm
    k_nope = c Wk_b;  v = c Wv_b;        (Wk_b, Wv_b: kv_b_proj's halves)
    q_pe, k_pe rotated (YaRN frequencies), k_pe shared by every head
    o = softmax((q_nope.k_nope + q_pe.k_pe) * scale, causal) v;  out = o Wo

with ``scale = (qk_nope + qk_rope) ** -0.5 * mscale ** 2``, ``mscale =
0.1 * mscale_all_dim * ln(factor) + 1``, and YaRN's frequencies: the
base frequencies ``theta ** (-2i / qk_rope)`` blended with themselves
divided by ``factor`` along a linear ramp between the dims that turn
``beta_fast`` and ``beta_slow`` times over the original context.

The MoE FFN: sigmoid scores of the router over every one of the
deployment's ``n_routed_experts`` experts; the choice (``noaux_tc``)
adds the correction bias, ranks the ``n_group`` groups by the sum of
their two best biased scores, keeps ``topk_group`` of them and takes
the ``num_experts_per_tok`` best experts inside; the gates are the
chosen experts' unbiased scores, normalized to sum to one and scaled by
``routed_scaling_factor``.  The output is the shared expert's plus the
gated outputs of the routed experts this chip holds (``held``); what
the experts held elsewhere would add is left out, as the chip's share
of an expert-parallel layer leaves it out.

Departures from the published description, none of which changes the
model: the rope dims are rotated as two halves where the published code
rotates interleaved pairs (the same model under a fixed permutation of
the random weights' rope columns); ``kv_b_proj`` is kept as its key and
value halves; every weight, the correction bias included, is stored in
bfloat16.  The multi-token-prediction module is not served, so it is
not here.

Everything is float32, every matmul at ``Precision.HIGHEST``; a layer's
bfloat16 weights are upcast inside its step of a scan over the layers,
and attention runs in blocks of queries, so that a pass over 7168
positions fits one 16 GB chip (a whole 128-head score matrix is ~26 GB).
The ``fp8`` variant rounds both operands of every matmul to float8_e4m3
(per-tensor scale for weights, per-row for activations): the control
that a lower precision must fail.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 128           # queries per attention block
ROW_BLOCK = 1024        # rows per FFN block


def key_from_seed(seed: int):
    """A PRNG key holding every bit of a non-negative ``seed``."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def held(c: Dict) -> range:
    """The routed experts this chip holds, of the deployment's."""
    first = c["deployment"]["first_held_expert"]
    return range(first, first + c["n_routed_experts"])


def shapes(c: Dict) -> Dict[str, tuple]:
    """Shape of each weight of config ``c`` (published key names):
    ``d.*`` stacked over the dense layers, ``m.*`` over the MoE ones."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    ql, kvl = c["q_lora_rank"], c["kv_lora_rank"]
    nd = c["first_k_dense_replace"]
    nm = c["num_hidden_layers"] - nd
    s = {"embed": (c["vocab_size"], d), "head": (d, c["vocab_size"]),
         "final_norm": (d,)}
    for g, n in (("d", nd), ("m", nm)):
        s.update({f"{g}.attn_norm": (n, d), f"{g}.mlp_norm": (n, d),
                  f"{g}.wq_a": (n, d, ql), f"{g}.q_norm": (n, ql),
                  f"{g}.wq_b": (n, ql, h, nope + rope),
                  f"{g}.wkv_a": (n, d, kvl + rope), f"{g}.kv_norm": (n, kvl),
                  f"{g}.wk_b": (n, kvl, h, nope),
                  f"{g}.wv_b": (n, kvl, h, vd), f"{g}.wo": (n, h, vd, d)})
    ff, e = c["intermediate_size"], c["moe_intermediate_size"]
    s.update({"d.w_gate": (nd, d, ff), "d.w_up": (nd, d, ff),
              "d.w_down": (nd, ff, d)})
    ne, nh = c["deployment"]["n_routed_experts"], c["n_routed_experts"]
    s.update({"m.router": (nm, d, ne), "m.router_bias": (nm, ne),
              "m.w_gate": (nm, nh, d, e), "m.w_up": (nm, nh, d, e),
              "m.w_down": (nm, nh, e, d),
              "m.s_gate": (nm, d, e * c["n_shared_experts"]),
              "m.s_up": (nm, d, e * c["n_shared_experts"]),
              "m.s_down": (nm, e * c["n_shared_experts"], d)})
    return s


def make_weights(c: Dict, key) -> Dict[str, jnp.ndarray]:
    """Random weights in bfloat16.  Projections are normal with std
    ``fan_in ** -0.5`` (the fan-in of a head's projection is the rank or
    width it reads), so each block adds O(1) per element to the
    residual; the embedding has std 1; norm scales are 1 + 0.1 N(0, 1),
    so that a program that skipped one would differ; the router's
    correction bias is 0.1 N(0, 1), the size of the spread of its
    sigmoid scores, so that it moves the choice.  Call under
    ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(c).items())):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        base = name.split(".")[-1]
        if name == "embed":
            w = z
        elif base.endswith("norm"):
            w = 1.0 + 0.1 * z
        elif base == "router_bias":
            w = 0.1 * z
        elif base in ("wq_b", "wk_b", "wv_b"):      # (.., rank, heads, hd)
            w = z / math.sqrt(shape[-3])
        elif base == "wo":                          # (.., heads, hd, d)
            w = z / math.sqrt(shape[-3] * shape[-2])
        elif name == "m.w_down":                    # routed experts
            w = z / math.sqrt(shape[-2]) / c["routed_scaling_factor"]
        else:                                       # (.., fan_in, out)
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


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn(c):
    """(inverse frequencies of the rope dims' pairs, softmax factor)."""
    rs, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    freqs = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    freqs = freqs / rs["factor"] * ramp + freqs * (1.0 - ramp)
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return freqs, mscale * mscale


def _rope(x, pos, freqs):
    """x: (S, heads, rope): rotate the two halves of the rope dims."""
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _by_rows(fn, x, block: int):
    """``fn`` over blocks of ``block`` rows of x (S, ...), one at a time."""
    s = x.shape[0]
    b = math.gcd(s, block)
    out = jax.lax.map(fn, x.reshape(s // b, b, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def _mla(c, lw, x, pos, fp8):
    h = c["num_attention_heads"]
    nope, kvl, eps = c["qk_nope_head_dim"], c["kv_lora_rank"], \
        c["rms_norm_eps"]
    freqs, mfac = _yarn(c)
    scale = mfac / math.sqrt(nope + c["qk_rope_head_dim"])
    s = x.shape[0]
    cq = _rms(_mm(x, lw["wq_a"], fp8), lw["q_norm"], eps)
    q = _mm(cq, lw["wq_b"].reshape(cq.shape[-1], -1), fp8).reshape(s, h, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, freqs)],
                        -1)
    kv = _mm(x, lw["wkv_a"], fp8)
    lat = _rms(kv[:, :kvl], lw["kv_norm"], eps)
    k_pe = _rope(kv[:, None, kvl:], pos, freqs)
    k_nope = _mm(lat, lw["wk_b"].reshape(kvl, -1), fp8).reshape(s, h, -1)
    v = _mm(lat, lw["wv_b"].reshape(kvl, -1), fp8).reshape(s, h, -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (s, h, k_pe.shape[-1]))], -1)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    qb = math.gcd(s, Q_BLOCK)

    def block(args):
        qi, p0 = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * scale
        causal = (p0 + jnp.arange(qb))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        if fp8:
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(s // qb, qb, h, -1),
                            jnp.arange(0, s, qb)))
    o = o.reshape(s, -1)
    return _mm(o, lw["wo"].reshape(o.shape[-1], -1), fp8)


def _swiglu(x, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(x, wg, fp8)) * _mm(x, wu, fp8), wd, fp8)


def _moe(c, lw, x, fp8):
    """The shared expert plus the held experts' gated part."""
    ng, kg, k = c["n_group"], c["topk_group"], c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(x, lw["router"], fp8))            # (S, E)
    pick = (scores + lw["router_bias"]).reshape(x.shape[0], ng, -1)
    group = jnp.sort(pick, -1)[..., -2:].sum(-1)                  # (S, G)
    gate_of_group = jnp.sort(group, -1)[:, -kg][:, None]
    pick = jnp.where((group >= gate_of_group)[..., None], pick, -jnp.inf)
    top = jax.lax.top_k(pick.reshape(x.shape[0], -1), k)[1]       # (S, k)
    w = jnp.take_along_axis(scores, top, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    out = _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"], fp8)
    for j, e in enumerate(held(c)):
        g = jnp.sum(jnp.where(top == e, w, 0.0), -1, keepdims=True)
        out = out + g * _swiglu(x, lw["w_gate"][j], lw["w_up"][j],
                                lw["w_down"][j], fp8)
    return out


def logits_at(c: Dict, w: Dict, tokens, at, fp8: bool = False):
    """Logits (len(at), vocab) float32 after ``tokens`` (S,), read at
    positions ``at``.  Causal, so padding after the last position read
    changes nothing."""
    f32 = lambda a: a.astype(jnp.float32)
    eps = c["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])
    x = f32(w["embed"])[tokens]

    def layer(moe: bool):
        def step(x, lw):
            lw = {n: f32(a) for n, a in lw.items()}
            x = x + _mla(c, lw, _rms(x, lw["attn_norm"], eps), pos, fp8)
            if moe:
                ffn = lambda r: _moe(c, lw, _rms(r, lw["mlp_norm"], eps),
                                     fp8)
            else:
                ffn = lambda r: _swiglu(_rms(r, lw["mlp_norm"], eps),
                                        lw["w_gate"], lw["w_up"],
                                        lw["w_down"], fp8)
            return x + _by_rows(ffn, x, ROW_BLOCK), None
        return step

    for g, moe in (("d", False), ("m", True)):
        group = {n[2:]: a for n, a in w.items() if n.startswith(g + ".")}
        x, _ = jax.lax.scan(layer(moe), x, group)
    x = _rms(x[at], f32(w["final_norm"]), eps)
    return _mm(x, f32(w["head"]), fp8)
