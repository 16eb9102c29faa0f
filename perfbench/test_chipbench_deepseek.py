"""DeepSeek-V3 at the tiny cut of its benchmark configuration, against
the plain float32 reference (``reference/deepseek-v3.py``) on seeded
random weights: paged prefill and decode through the serve steps,
compared on logits; the expert-parallel share of a MoE layer; the
``noaux_tc`` routing; YaRN's frequencies and softmax scale.

The program runs float32 activations here (the tiny cut's ``dtype``)
over the bf16 weights: at these widths a bf16 rounding that flips a
routing choice at a near-tie moves a logit by up to ~0.3, as far as the
fp8 control does, which would hide what these comparisons look for.
One run at the stated bf16 activations is judged on a limit of its own
(``test_bf16_activations_serve_within_their_readings``)."""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import harness
import program
from repro.configs.base import MoEConfig, YarnConfig
from repro.models import layers, mla, moe
from repro.models import model as model_mod

NAME = "deepseek-v3"


@pytest.fixture(scope="module")
def tiny():
    c = conftest.tiny_config(NAME)
    ref = harness.reference(harness.HERE, c)
    fam = harness.family(harness.HERE, c)
    cfg = program.model_config(c, fam)
    key = ref.key_from_seed(2**31 + 19)
    w = jax.jit(lambda k: ref.make_weights(c, k))(key)
    params = jax.jit(lambda k: fam.layout(c, ref.make_weights(c, k)))(key)
    program.check_layout(cfg, params)
    return c, ref, cfg, w, params


def test_tiny_cut_keeps_the_family(tiny):
    """A dense layer and two MoE layers; a share of the experts held;
    group-limited sigmoid routing; YaRN; no MTP."""
    c, _, cfg, _, _ = tiny
    m = cfg.moe
    assert (cfg.num_layers, m.first_dense_layers) == (3, 1)
    assert (m.num_experts, m.held, m.top_k) == (64, 8, 8)
    assert (m.n_group, m.topk_group) == (8, 4)
    assert (m.scoring_func, m.topk_method) == ("sigmoid", "noaux_tc")
    assert cfg.rope_scaling is not None and not cfg.mtp


def test_paged_prefill_then_decode_matches_reference(tiny):
    """Two rows prefilled in chunks of 16 (one a passenger of the
    other's last chunk) and decoded 6 steps through the paged step
    programs: every logit row agrees with the reference's full forward
    pass over the same tokens."""
    c, ref, cfg, w, params = tiny
    ps, chunk, max_len = 8, 16, 96
    fns = model_mod.make_paged_serve_fns(cfg)
    prefill, decode = jax.jit(fns.prefill_chunk), jax.jit(fns.decode)
    nb = max_len // ps
    table = np.arange(1, 1 + 2 * nb, dtype=np.int32).reshape(2, nb)
    # float32 pools: a bf16 row rounds to ~1e-3 of the logits
    pools = model_mod.init_paged_caches(cfg, 1 + table.size, ps,
                                        dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, c["vocab_size"], n) for n in (37, 20)]
    seqs = [list(p) for p in prompts]
    got = [[], []]
    for off in range(0, 48, chunk):
        toks = np.zeros((2, chunk), np.int32)
        last = np.full((2,), -1, np.int32)
        for j, p in enumerate(prompts):
            if off < len(p):
                part = p[off:off + chunk]
                toks[j, :len(part)] = part
                last[j] = len(part) - 1
        logits, pools, counts = prefill(
            params, pools, jnp.asarray(toks), jnp.full((2,), off, jnp.int32),
            jnp.asarray(last), jnp.asarray(table))
        assert counts.shape == (cfg.moe.held + 1,)      # over 2 MoE layers
        assert int(counts[-1]) == 2 * cfg.moe.top_k * int((last + 1).sum())
        for j, p in enumerate(prompts):
            if off < len(p) <= off + chunk:
                got[j].append(np.asarray(logits[j]))
    pos = np.array([len(p) for p in prompts], np.int32)
    for _ in range(6):
        nxt = np.array([int(np.argmax(g[-1])) for g in got], np.int32)
        for j in range(2):
            seqs[j].append(int(nxt[j]))
        logits, pools, _ = decode(params, pools, jnp.asarray(nxt[:, None]),
                                  jnp.asarray(pos), jnp.asarray(table))
        for j in range(2):
            got[j].append(np.asarray(logits[j]))
        pos += 1
    for j, p in enumerate(prompts):
        at = jnp.arange(len(p) - 1, len(seqs[j]))
        want = np.asarray(ref.logits_at(c, w, jnp.asarray(seqs[j]), at))
        err = np.abs(np.stack(got[j]) - want).max() / np.abs(want).max()
        assert err < 1e-4, (j, err)


def test_engine_serves_the_reference_tokens(tiny):
    """Two requests through ``ServeEngine`` as the benchmark runs it
    (paged, chunked prefill, greedy, float32 pool): each served token is
    the reference's best, or within rounding of it, at its position."""
    from repro.serve.engine import Request, ServeEngine
    c, ref, cfg, w, params = tiny
    eng = ServeEngine(cfg, params, batch_size=2, max_len=128,
                      kv_layout="paged", kv_page_size=8, prefill_chunk=16,
                      greedy=True, cache_dtype="float32")
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, c["vocab_size"], n).tolist(),
                    max_new_tokens=k) for n, k in ((45, 9), (18, 14))]
    eng.generate(reqs)
    for r in reqs:
        assert len(r.out) == r.max_new_tokens
        seq = r.prompt + r.out[:-1]
        at = jnp.arange(len(r.prompt) - 1, len(seq))
        lg = np.asarray(ref.logits_at(c, w, jnp.asarray(seq), at))
        gaps = lg.max(-1) - lg[np.arange(len(r.out)), r.out]
        assert gaps.max() < 1e-3 * np.abs(lg).max(), gaps
    st = eng.stats()
    assert len(st["moe_held_assignments"]) == cfg.moe.held
    assert sum(st["moe_held_assignments"]) <= st["moe_assignments"]


# bf16 activations, the tiny cut, 12 seeds (1-8, 31, 2^31+3, 2^31+7,
# 3000000019) on the CPU: served 0.014-0.319, the fp8 control
# 0.287-0.549.  The limit lies above every served reading; a fault of
# the bf16 path (a lost cast, a wrong scale) reads several units.  It
# does not tell bf16 from fp8 at this size: the float32 cut does that.
BF16_MAX_LOGIT_GAP = 0.5


def test_bf16_activations_serve_within_their_readings(tiny_root):
    """The benchmark's tiny DeepSeek cell at the configuration's own
    bf16 activations: ``correct`` under the bf16 limit, on the seed
    whose reading was the widest."""
    path = tiny_root / "perfbench" / "configs" / f"{NAME}.json"
    c = json.loads(path.read_text())
    c["program"]["overrides"]["dtype"] = "bfloat16"
    c["correct"]["max_logit_gap"] = BF16_MAX_LOGIT_GAP
    path.write_text(json.dumps(c))
    res = conftest.run_tiny(tiny_root, f"{NAME}.longbatch", seed=4)
    gap = res["checks"]["max_logit_gap"]["value"]
    assert res["correct"] is True, gap
    assert 0 < gap < BF16_MAX_LOGIT_GAP


def _bank(cfg, key):
    """Router, bias, every expert's weights and the shared expert, in
    float32, for a layer that holds all ``cfg.moe.num_experts``."""
    m, d = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 8)
    n = lambda k, s, f: jax.random.normal(k, s, jnp.float32) / math.sqrt(f)
    return {"router": n(ks[0], (d, m.num_experts), d),
            "router_bias": 0.1 * jax.random.normal(ks[1], (m.num_experts,)),
            "w_gate": n(ks[2], (m.num_experts, d, m.ff_dim), d),
            "w_up": n(ks[3], (m.num_experts, d, m.ff_dim), d),
            "w_down": n(ks[4], (m.num_experts, m.ff_dim, d), m.ff_dim),
            "shared": {"w_gate": n(ks[5], (d, m.ff_dim), d),
                       "w_up": n(ks[6], (d, m.ff_dim), d),
                       "w_down": n(ks[7], (m.ff_dim, d), m.ff_dim)}}


def test_expert_shares_add_up_to_the_uncut_layer(tiny):
    """Each of the ``n / held`` chips of the expert-parallel layer holds
    its block of experts and computes its part; the parts of all the
    shares, with the shared expert (which every chip computes alike)
    counted once, add up to the uncut layer."""
    _, _, cfg, _, _ = tiny
    cfg = dataclasses.replace(cfg, dtype="float32")
    m = cfg.moe
    whole = dataclasses.replace(m, held_experts=0, first_held_expert=0)
    p = _bank(cfg, jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, cfg.d_model))
    uncut, uc = moe.moe_serve(dataclasses.replace(cfg, moe=whole), p, x)
    shared = layers.apply_mlp(cfg, p["shared"], x)
    total, counts = shared, 0
    for first in range(0, m.num_experts, m.held):
        share = dataclasses.replace(m, first_held_expert=first)
        sl = lambda a: a[first:first + m.held]
        ps = dict(p, **{k: sl(p[k]) for k in ("w_gate", "w_up", "w_down")})
        out, c = moe.moe_serve(dataclasses.replace(cfg, moe=share), ps, x)
        total = total + out - shared
        counts = counts + int(c[:-1].sum())
        assert int(c[-1]) == x.shape[0] * x.shape[1] * m.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
    assert counts == int(uc[:-1].sum()) == int(uc[-1])


def _route_loop(m: MoEConfig, logits, bias):
    """DeepSeek-V3's ``noaux_tc`` choice, one token and group at a time."""
    gates, ids = [], []
    size = m.num_experts // m.n_group
    for row in logits:
        s = 1.0 / (1.0 + np.exp(-row.astype(np.float64)))
        b = s + bias
        groups = [sorted(b[g * size:(g + 1) * size])[-2:]
                  for g in range(m.n_group)]
        ranked = sorted(range(m.n_group), key=lambda g: -sum(groups[g]))
        kept = ranked[:m.topk_group]
        cand = [e for e in range(m.num_experts) if e // size in kept]
        top = sorted(cand, key=lambda e: -b[e])[:m.top_k]
        g = np.array([s[e] for e in top])
        if m.norm_topk_prob:
            g = g / g.sum()
        gates.append(g * m.routed_scaling_factor)
        ids.append(top)
    return np.array(gates), np.array(ids)


@pytest.mark.parametrize("shape", [(64, 8, 2, 1, 2), (128, 256, 8, 4, 8)])
def test_noaux_tc_routing_matches_a_loop_over_groups(shape):
    """(tokens, experts, groups, groups kept, experts per token)."""
    n, e, groups, kept, k = shape
    m = MoEConfig(num_experts=e, top_k=k, ff_dim=4, scoring_func="sigmoid",
                  topk_method="noaux_tc", n_group=groups, topk_group=kept,
                  routed_scaling_factor=2.5)
    d = 16
    cfg = type("Cfg", (), {"moe": m})()
    rng = np.random.default_rng(11)
    x = rng.normal(size=(n, d)).astype(np.float32)
    p = {"router": rng.normal(size=(d, m.num_experts)).astype(np.float32)
         / 4, "router_bias": 0.1 * rng.normal(
             size=(m.num_experts,)).astype(np.float32)}
    gates, ids, _ = moe.route(cfg, p, jnp.asarray(x))
    want_g, want_i = _route_loop(m, x @ p["router"], p["router_bias"])
    order = np.argsort(np.asarray(ids), -1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(ids), order, -1), np.sort(want_i, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(want_g, np.argsort(want_i, -1), -1), rtol=1e-5)


def test_yarn_frequencies_and_scale_are_the_closed_form():
    y = YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                   mscale_all_dim=1.0)
    dim, base = 64, 10000.0
    got = np.asarray(layers.rope_frequencies(dim, base, y))
    # the dims turning 32 and 1 times over 4096 positions
    corr = lambda r: dim * math.log(4096 / (r * 2 * math.pi)) / (
        2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        f = base ** (-2 * i / dim)
        t = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f / 40 * t + f * (1 - t), rel=1e-5)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        base ** (-(dim - 2) / dim) / 40, rel=1e-5)
    factor = (0.1 * math.log(40) + 1) ** 2
    assert layers.yarn_softmax_factor(y) == pytest.approx(factor)
    assert factor == pytest.approx(1.8738, abs=1e-4)
    assert layers.yarn_softmax_factor(None) == 1.0


def test_mla_softmax_scale_carries_yarn(tiny):
    _, _, cfg, _, _ = tiny
    m = cfg.mla
    assert mla.softmax_scale(cfg) == pytest.approx(
        (0.1 * math.log(40) + 1) ** 2
        / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim))


def test_deepseek_counts_follow_the_published_sizes():
    """DeepSeek-V3's counts from its sizes, worked out by hand: MLA 187M
    parameters a layer in the absorbed form (the same count as the
    published one at these widths), the dense FFN 396M, a MoE layer's
    router, shared expert and 8/256 of a token's 8 routed experts;
    attention 2 * (576 + 512) operations and one 1152-byte latent row a
    key, per head and layer."""
    entry = {c["name"]: c for c in conftest.benchmark()["configs"]}[NAME]
    c = harness.load_json(harness.CHECKOUT / entry["file"])
    fam = harness.family(harness.HERE, c)
    mla_params = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                  + 2 * 128 * 128 * 512 + 128 * 128 * 7168)
    assert mla_params == 187_105_280
    dense, expert = 3 * 7168 * 18432, 3 * 7168 * 2048
    moe_params = 7168 * 256 + expert * (1 + 8 * 8 / 256)
    assert fam.matmul_flops_per_token(c) == 2 * (
        7 * mla_params + 3 * dense + 4 * moe_params)
    assert fam.head_flops(c) == 2 * 7168 * 16160
    assert fam.attn_flops(c, 1000) == 2 * (576 + 512) * 128 * 7 * 1000
    qo = 128 * (576 + 512) * 2
    assert fam.attn_bytes(c, 1000, 1) == 7 * (576 * 2 * 1000 + qo)
