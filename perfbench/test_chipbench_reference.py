"""The plain reference against the program at the tiny size, for every
configuration and cell of ``BENCHMARK.json``: the program's own forward
pass and its served tokens."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import harness
import program


@pytest.mark.parametrize(
    "name", [c["name"] for c in conftest.benchmark()["configs"]])
def test_reference_matches_program_forward(name):
    from repro.models import layers
    from repro.models import model as model_mod
    c = conftest.tiny_config(name)
    ref = harness.reference(harness.HERE, c)
    fam = harness.family(harness.HERE, c)
    cfg = program.model_config(c, fam)
    key = ref.key_from_seed(2**31 + 7)
    w = jax.jit(lambda k: ref.make_weights(c, k))(key)
    params = jax.jit(lambda k: fam.layout(c, ref.make_weights(c, k)))(key)
    program.check_layout(cfg, params)
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 40)
    want = ref.logits_at(c, w, jnp.asarray(toks), jnp.arange(40))
    hidden = model_mod.build_forward(cfg)(
        params, {"tokens": jnp.asarray(toks)[None]})
    hidden = hidden[0] if isinstance(hidden, tuple) else hidden
    got = layers.logits_from_hidden(cfg, params["embed"], hidden)[0]
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want))) / scale
    # bf16 activations against float32: a few bf16 steps (2^-8)
    assert err < 0.05
    low = ref.logits_at(c, w, jnp.asarray(toks), jnp.arange(40), fp8=True)
    assert float(jnp.max(jnp.abs(low - want))) / scale > err


@pytest.mark.parametrize(
    "cell", [w["name"] for w in conftest.benchmark()["workloads"]])
def test_served_tokens_agree_with_reference(tiny_root, cell):
    res = conftest.run_tiny(tiny_root, cell, seed=2**31 + 3)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
