"""Mixture-of-Experts layer: sort-based dispatch, static capacity, EP-sharded.

Used by kimi-k2 (384e top-8 + 1 shared), deepseek-v3 (256e top-8 + 1 shared,
first 3 layers dense) and jamba (16e top-2, MoE every other layer).

Dispatch algorithm (TPU-native adaptation of sort-based/MegaBlocks-style
dispatch; DESIGN.md §6):

  1. tokens are grouped along the batch axis into G groups that align with
     the data shards, so routing/sorting is *local* to a shard;
  2. per group: router top-k -> (token, expert) assignments, sorted by
     expert id; rank-within-expert via searchsorted; assignments whose
     rank exceeds the static capacity C are dropped (token keeps shared-
     expert + residual path only);
  3. an inverse index ``token_for_slot (E*C,)`` gathers tokens into the
     expert buffer (G, E, C, d) — the only O(E*C*d) tensor; there is no
     (T*k, d) intermediate;
  4. expert FFNs run as one einsum with experts sharded on the "model"
     mesh axis (EP);
  5. combine is a scatter-add back to token layout weighted by the gate —
     under GSPMD this lowers to partial scatters + an all-reduce over the
     expert axis, the standard GShard combine collective.

Dispatch FLOPs are therefore ~ active FLOPs x capacity_factor, never
num_experts x dense FLOPs.

Serving, and a layer that holds a share of the experts (expert
parallelism: ``MoEConfig.held_experts``), take the drop-free path
instead (``moe_serve``): the router scores every expert, and the layer
computes every assignment to the experts it holds, so no capacity is
shared across a batch and a row's output never depends on the rows
beside it.  Assignments to experts held elsewhere add
nothing here.  A step of ``GROUPED_MIN_ROWS`` rows or more, in a layer
that holds more experts than a token picks (``top_k < held``: Kimi-K2,
Jamba), runs each held expert over the rows routed to it, sorted by
expert, as one grouped matmul (``lax.ragged_dot``; its work on a TPU
follows the routed rows, and under the layer scan it reads each weight
copied out of the stack).  Otherwise the held experts run as one
batched FFN over every row of the step: below that many rows the FFN
costs what reading the experts' weights costs, and a layer holding no
more than ``top_k`` experts (one chip's share of DeepSeek-V3) would
get a grouped buffer no smaller (a 64 x 32 prefill chunk there
computes 32x its routed work, PERF.md).  That FFN sits under a
``lax.cond`` that skips it when no row is routed to the held experts;
on the device trace the conditional is the ``moe/experts`` scope's
time, and the router's ops carry ``moe/route``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import layers
from repro.sharding.specs import annotate, shard


# -- params -------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    """Router + expert bank (+ optional shared experts as one fused MLP)."""
    m = cfg.moe
    d, e, ff, held = cfg.d_model, m.num_experts, m.ff_dim, m.held
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": annotate(layers.dense_init(k1, (d, e)), "d_model",
                           "experts"),
        "w_up": annotate(_expert_init(k2, (held, d, ff)), "experts",
                         "d_model", "ffn"),
        "w_gate": annotate(_expert_init(k3, (held, d, ff)), "experts",
                           "d_model", "ffn"),
        "w_down": annotate(_expert_init(k4, (held, ff, d), in_axis=1),
                           "experts", "ffn", "d_model"),
    }
    if m.topk_method == "noaux_tc":
        # the per-expert correction bias: added to the scores for the
        # choice of experts only, never to the gates
        p["router_bias"] = annotate(jnp.zeros((e,), jnp.float32), None)
    if m.num_shared_experts:
        p["shared"] = layers.init_mlp(k5, cfg, d=d,
                                      ff=m.num_shared_experts * ff)
    return p


def _expert_init(key, shape, in_axis: int = 1):
    std = 1.0 / math.sqrt(shape[in_axis])
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


# -- static sizing ------------------------------------------------------------

# Rows of a step from which the held experts run as a grouped matmul over
# their routed rows (when top_k < held): about a TPU v5e's ridge, 197
# TFLOP/s over 819 GB/s, the rows at which an FFN over every row stops
# costing only its weights' reading.
GROUPED_MIN_ROWS = 256


def moe_groups(cfg: ModelConfig, batch: int) -> int:
    """Number of routing groups: the largest power-of-two divisor of the
    batch that does not exceed the data-shard count (32 on the production
    mesh). Groups align with data shards so sorting stays shard-local."""
    g = math.gcd(batch, 32)
    return max(1, g)


def capacity(tokens_per_group: int, m: MoEConfig) -> int:
    c = math.ceil(tokens_per_group * m.top_k * m.capacity_factor
                  / m.num_experts)
    return max(1, min(c, tokens_per_group * m.top_k))


# -- routing -------------------------------------------------------------------

def route(cfg: ModelConfig, p, xg: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Router scores and top-k choice, in float32, over all
    ``num_experts`` (``MoEConfig`` describes the two rules).

    xg: (..., d) -> gates (..., k) fp32, expert ids (..., k) int32,
    scores (..., E) fp32 (for the aux loss).
    """
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if m.scoring_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    choose = scores
    if "router_bias" in p:
        choose = choose + p["router_bias"].astype(jnp.float32)
    if m.n_group > 1:
        grouped = choose.reshape(*choose.shape[:-1], m.n_group, -1)
        if m.topk_method == "noaux_tc":
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
        else:
            group_score = grouped.max(-1)
        _, kept = jax.lax.top_k(group_score, m.topk_group)
        keep = jax.nn.one_hot(kept, m.n_group, dtype=jnp.bool_).any(-2)
        choose = jnp.where(keep[..., None], grouped,
                           -jnp.inf).reshape(choose.shape)
    _, top_i = jax.lax.top_k(choose, m.top_k)
    top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    if m.norm_topk_prob:
        # deepseek/kimi renormalize the selected gate weights
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    gates = top_p * m.routed_scaling_factor
    return gates, top_i.astype(jnp.int32), scores


def aux_loss(probs: jnp.ndarray, top_i: jnp.ndarray, num_experts: int
             ) -> jnp.ndarray:
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    e = num_experts
    counts = jax.nn.one_hot(top_i, e, dtype=jnp.float32).sum((1, 2))  # (G,E)
    f = counts / jnp.maximum(counts.sum(-1, keepdims=True), 1.0)
    pbar = probs.mean(1)                                              # (G,E)
    return (e * (f * pbar).sum(-1)).mean()


# -- dispatch indices (per group, vmapped) --------------------------------------

def _dispatch_indices(top_i: jnp.ndarray, cap: int, num_experts: int):
    """Sort-based dispatch plan for one group.

    top_i: (T, k) expert ids. Returns
      token_for_slot: (E*C,) token index feeding each expert slot
                      (sentinel T when the slot is empty),
      slot_for_tk:    (T, k) slot index of each assignment
                      (sentinel E*C when dropped at capacity).
    """
    t, k = top_i.shape
    flat_e = top_i.reshape(-1)                       # (T*k,)
    flat_t = jnp.arange(t * k, dtype=jnp.int32) // k  # token of assignment
    order = jnp.argsort(flat_e, stable=True)
    sid = jnp.take(flat_e, order)
    stok = jnp.take(flat_t, order)
    first = jnp.searchsorted(sid, sid, side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = rank < cap
    slot = jnp.where(keep, sid * cap + rank, num_experts * cap)

    token_for_slot = jnp.full((num_experts * cap + 1,), t, jnp.int32)
    token_for_slot = token_for_slot.at[slot].set(stok, mode="drop")
    token_for_slot = token_for_slot[:num_experts * cap]

    slot_for_flat = jnp.zeros((t * k,), jnp.int32).at[order].set(slot)
    return token_for_slot, slot_for_flat.reshape(t, k)


# -- the layer -------------------------------------------------------------------

def held_gates(cfg: ModelConfig, gates, top_i):
    """(..., held) gate of each row for each held expert (0 where the
    row is not routed to it)."""
    m = cfg.moe
    ids = m.first_held_expert + jnp.arange(m.held, dtype=jnp.int32)
    hit = top_i[..., None] == ids                     # (..., k, held)
    return jnp.sum(jnp.where(hit, gates[..., None], 0.0), axis=-2), \
        hit.any(-2)


def _held_experts(p, x2, w):
    """The held experts' part of the result: x2 (T, d), w (T, held)
    gates -> (T, d).  Every row goes through every held expert; the
    gates scale the hidden rows before the down projection, so rows
    routed elsewhere add exactly zero."""
    dt = x2.dtype
    up = jnp.einsum("td,edf->etf", x2, p["w_up"].astype(dt))
    gate = jnp.einsum("td,edf->etf", x2, p["w_gate"].astype(dt))
    h = jax.nn.silu(gate) * up * w.T[:, :, None].astype(dt)
    return jnp.einsum("etf,efd->td", h, p["w_down"].astype(dt))


def _held_grouped(m: MoEConfig, p, x2, gates, top_i, ok, sizes):
    """The held experts' part of the result, over the routed rows only:
    x2 (T, d); gates, top_i (T, k); ok (T,) the valid rows; sizes
    (held,) the valid rows' assignments to each held expert -> (T, d).
    The assignments are sorted by held expert, the others last, and
    past ``sum(sizes)`` the grouped matmul's rows are not read."""
    dt = x2.dtype
    k = top_i.shape[-1]
    local = top_i - m.first_held_expert
    keep = (local >= 0) & (local < m.held) & ok[:, None]
    key = jnp.where(keep, local, m.held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rows = order // k
    xs = jnp.take(x2, rows, axis=0)
    up = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), sizes)
    gate = jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), sizes)
    g = jnp.take(gates.reshape(-1), order)
    h = jax.nn.silu(gate) * up * g[:, None].astype(dt)
    y = jax.lax.ragged_dot(h, p["w_down"].astype(dt), sizes)
    y = jnp.where(jnp.take(keep.reshape(-1), order)[:, None],
                  y.astype(jnp.float32), 0.0)
    return jnp.zeros(x2.shape, jnp.float32).at[rows].add(y).astype(dt)


def moe_serve(cfg: ModelConfig, p, x: jnp.ndarray, valid=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-free MoE FFN.  x: (B, S, d); ``valid`` (B, S) bool marks the
    rows that are real tokens (all when None); the others are routed to
    no expert.  Returns (out (B, S, d), counts (held + 1,) int32): the
    valid rows' assignments to each held expert, then all their routed
    assignments.

    With ``top_k < held`` and ``GROUPED_MIN_ROWS`` rows or more, the
    held experts run over their routed rows as one grouped matmul;
    otherwise over every row, under a ``lax.cond`` that skips them when
    no valid row is routed to them.  On the device trace that
    conditional is the experts' time (the ``moe/experts`` scope); a
    gather of each expert's rows into a fixed buffer would need a
    second branch for the batches that overflow it, and XLA hands each
    branch a copy of the layer's expert weights out of the layer
    stack."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    with jax.named_scope("moe/route"):
        gates, top_i, _ = route(cfg, p, x2)
        w, hit = held_gates(cfg, gates, top_i)
        ok = jnp.ones((t,), jnp.bool_) if valid is None \
            else valid.reshape(t)
        hit = hit & ok[:, None]
        w = jnp.where(hit, w, 0.0)
        counts = jnp.concatenate(
            [jnp.sum(hit, axis=0, dtype=jnp.int32),
             (jnp.sum(ok, dtype=jnp.int32) * m.top_k)[None]])
    experts = {k: p[k] for k in ("w_up", "w_gate", "w_down")}
    with jax.named_scope("moe/experts"):
        if m.top_k < m.held and t >= GROUPED_MIN_ROWS:
            out = _held_grouped(m, experts, x2, gates, top_i, ok,
                                counts[:-1])
        else:
            out = jax.lax.cond(jnp.any(hit), _held_experts,
                               lambda p_, x_, w_: jnp.zeros_like(x_),
                               experts, x2, w)
    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + layers.apply_mlp(cfg, p["shared"], x)
    return out, counts


def apply_moe(cfg: ModelConfig, p, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN. x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    Capacity-limited sort-based dispatch for a layer that holds every
    expert; a layer that holds a share runs drop-free (``moe_serve``).
    """
    m = cfg.moe
    if m.held < m.num_experts:
        out, _ = moe_serve(cfg, p, x)
        _, top_i, probs = route(cfg, p, x.reshape(1, -1, x.shape[-1]))
        return out, aux_loss(probs, top_i, m.num_experts).astype(
            jnp.float32)
    b, s, d = x.shape
    dt = x.dtype
    g = moe_groups(cfg, b)
    tg = (b // g) * s
    cap = capacity(tg, m)
    e = m.num_experts

    xg = x.reshape(g, tg, d)
    xg = shard(xg, "batch", None, "d_model")
    gates, top_i, probs = route(cfg, p, xg)
    loss = aux_loss(probs, top_i, e)

    token_for_slot, slot_for_tk = jax.vmap(
        lambda ti: _dispatch_indices(ti, cap, e))(top_i)

    # dispatch: gather tokens into the expert buffer (sentinel row is zero)
    xpad = jnp.concatenate([xg, jnp.zeros((g, 1, d), dt)], axis=1)
    buf = jnp.take_along_axis(
        xpad, token_for_slot[:, :, None], axis=1)        # (G, E*C, d)
    buf = buf.reshape(g, e, cap, d)
    buf = shard(buf, "batch", "experts", None, "d_model")

    # expert FFN (EP einsum; experts sharded on "model")
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    gate = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt))
    h = jax.nn.silu(gate) * up
    h = shard(h, "batch", "experts", None, "ffn")
    y = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt))
    y = y.reshape(g, e * cap, d)

    # combine: gate-weighted scatter-add back to token layout.
    gate_for_slot = jnp.zeros((g, e * cap + 1), jnp.float32)
    gate_for_slot = jax.vmap(lambda z, sl, gt: z.at[sl.reshape(-1)].set(
        gt.reshape(-1), mode="drop"))(gate_for_slot, slot_for_tk, gates)
    y = y * gate_for_slot[:, :e * cap, None].astype(dt)

    out = jnp.zeros((g, tg + 1, d), dt)
    out = jax.vmap(lambda o, tok, yy: o.at[tok].add(yy, mode="drop"))(
        out, token_for_slot, y)
    out = out[:, :tg].reshape(b, s, d)
    out = shard(out, "batch", "seq", "d_model")

    if "shared" in p:
        out = out + layers.apply_mlp(cfg, p["shared"], x)
    return out, loss.astype(jnp.float32)


def is_moe_layer(cfg: ModelConfig, layer_idx: int) -> bool:
    """Whether layer ``layer_idx`` uses the MoE FFN (vs a dense MLP)."""
    m = cfg.moe
    if m is None:
        return False
    if layer_idx < m.first_dense_layers:
        return False
    return (layer_idx % m.every_k_layers) == m.moe_layer_offset
