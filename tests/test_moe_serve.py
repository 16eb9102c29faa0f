"""``moe.moe_serve``, the drop-free expert layer of the serve path: it
gives each row the gated sum of its held experts' outputs, and counts
the assignments of the valid rows."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import moe

D, FF = 16, 8


def _cfg(m: MoEConfig) -> ModelConfig:
    return ModelConfig(name="moe-serve-test", family="test", num_layers=1,
                       d_model=D, num_heads=2, num_kv_heads=2, d_ff=FF,
                       vocab_size=32, head_dim=8, moe=m, dtype="float32")


def _params(m: MoEConfig, key, hot: float = 0.0):
    """Router, the held experts' weights and (for ``noaux_tc``) the
    choice bias; ``hot`` biases the choice towards the first held
    expert."""
    ks = jax.random.split(key, 4)
    n = lambda k, s, f: jax.random.normal(k, s, jnp.float32) / math.sqrt(f)
    p = {"router": n(ks[0], (D, m.num_experts), D),
         "w_gate": n(ks[1], (m.held, D, FF), D),
         "w_up": n(ks[2], (m.held, D, FF), D),
         "w_down": n(ks[3], (m.held, FF, D), FF)}
    if m.topk_method == "noaux_tc":
        p["router_bias"] = jnp.zeros((m.num_experts,)).at[
            m.first_held_expert].set(hot)
    return p


def _row_loop(cfg, p, x2, valid):
    """Each valid row, each of its experts that is held, one at a time."""
    m = cfg.moe
    gates, ids, _ = moe.route(cfg, p, jnp.asarray(x2))
    gates, ids = np.asarray(gates), np.asarray(ids)
    out = np.zeros_like(x2)
    for t in range(x2.shape[0]):
        if not valid[t]:
            continue
        for g, e in zip(gates[t], ids[t]):
            j = e - m.first_held_expert
            if 0 <= j < m.held:
                wg, wu, wd = (np.asarray(p[k][j], np.float64)
                              for k in ("w_gate", "w_up", "w_down"))
                a = x2[t] @ wg
                out[t] += g * ((a / (1 + np.exp(-a)) * (x2[t] @ wu)) @ wd)
    return out


SIGMOID = MoEConfig(num_experts=64, top_k=4, ff_dim=FF, scoring_func="sigmoid",
                    topk_method="noaux_tc", n_group=4, topk_group=2,
                    routed_scaling_factor=2.5, held_experts=8,
                    first_held_expert=8)
SOFTMAX = MoEConfig(num_experts=16, top_k=2, ff_dim=FF)     # all held
# no more held experts than a token picks: the batched FFN over every row
SIGMOID_DENSE = MoEConfig(num_experts=64, top_k=8, ff_dim=FF,
                          scoring_func="sigmoid", topk_method="noaux_tc",
                          n_group=8, topk_group=4, routed_scaling_factor=2.5,
                          held_experts=8, first_held_expert=8)


@pytest.mark.parametrize("rows", [64, moe.GROUPED_MIN_ROWS * 2],
                         ids=["rows_few", "rows_grouped"])
@pytest.mark.parametrize("m,hot", [(SIGMOID, 0.0), (SIGMOID, 10.0),
                                   (SOFTMAX, 0.0), (SIGMOID_DENSE, 0.0),
                                   (SIGMOID_DENSE, 10.0)],
                         ids=["held_share", "one_hot_expert", "all_held",
                              "held_share_dense", "one_hot_expert_dense"])
def test_moe_serve_computes_every_held_assignment(m, hot, rows):
    """Every valid row gets each of its held experts' gated outputs (a
    share of 8 of 64, also with one expert taking every row, and a layer
    holding all 16), and the counts are those of the valid rows; by the
    grouped matmul over the routed rows when a step of many rows picks
    fewer experts a token than are held, else by the FFN over every
    row."""
    cfg = _cfg(m)
    p = _params(m, jax.random.PRNGKey(1), hot)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, rows // 2, D))
    valid = np.random.default_rng(3).random((2, rows // 2)) < 0.8
    out, counts = jax.jit(lambda p_, x_, v_: moe.moe_serve(cfg, p_, x_, v_))(
        p, x, jnp.asarray(valid))
    x2, v2 = np.asarray(x, np.float64).reshape(rows, D), valid.reshape(-1)
    _, ids, _ = moe.route(cfg, p, jnp.asarray(x2, jnp.float32))
    local = np.asarray(ids) - m.first_held_expert
    held = (local >= 0) & (local < m.held) & v2[:, None]
    per_expert = np.bincount(local[held], minlength=m.held)
    assert np.asarray(counts).tolist() == per_expert.tolist() + [
        int(v2.sum()) * m.top_k]
    want = _row_loop(cfg, p, x2, v2)
    np.testing.assert_allclose(np.asarray(out).reshape(rows, D), want,
                               rtol=2e-5, atol=2e-5)
