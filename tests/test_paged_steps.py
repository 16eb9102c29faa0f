"""Paged step programs against a per-layer loop over the same kernels.

``make_paged_serve_fns`` carries the stacked (L, P, page_size, ...)
pools through the layer scan and hands the kernels a layer index, so
no step slices a layer's pool out or stacks it back.  The oracle here
does what the step did before: it takes each layer's pool out as a
stack of one, runs the block at layer 0, and stacks the results.  Same
kernels, same arithmetic in the same order, so the logits and every
pool leaf must agree bitwise, scratch page included.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import blocks, layers
from repro.models import model as M

B, T, PS, NB = 3, 8, 4, 6
P = B * NB + 1

# (arch, config overrides): the layer scan (GQA, qk-norm), the unrolled
# loop, units of two layers with sliding windows and soft-capping, MLA
# with an unscanned prefix layer, and a quantized pool.
CASES = {
    "qwen3-scan": ("qwen3-0.6b", {}),
    "qwen3-loop": ("qwen3-0.6b", {"scan_layers": False}),
    "gemma2-units": ("gemma2-27b", {}),
    "deepseek-prefix-mla": ("deepseek-v3-671b", {}),
    "qwen3-int8": ("qwen3-0.6b", {"kv_quant": "int8"}),
}


def _setup(case):
    arch, over = CASES[case]
    cfg = dataclasses.replace(configs.get_config(arch, reduced=True),
                              dtype="float32", **over)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    pools = M.init_paged_caches(cfg, P, PS)
    leaves, tree = jax.tree.flatten(pools)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    filled = []
    for k, a in zip(keys, leaves):
        if a.dtype == jnp.int8:
            a = jax.random.randint(k, a.shape, -127, 128, jnp.int8)
        elif a.dtype == jnp.float32 and cfg.kv_quant is not None:
            a = jax.random.uniform(k, a.shape, jnp.float32, 0.01, 0.1)
        else:
            a = jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        filled.append(a)
    pools = jax.tree.unflatten(tree, filled)
    rng = np.random.default_rng(2)
    pt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * NB]
                     .reshape(B, NB), jnp.int32)
    pt = pt.at[2].set(0)                   # a dead row: scratch page only
    return cfg, params, pools, pt


def _per_layer(cfg, params, caches, x, run):
    """The sweep as a loop of one-layer pools: slice, run, restack."""
    lay = M.unit_layout(cfg)
    one = lambda tree: jax.tree.map(lambda a: a[None], tree)
    out = {}
    if lay.prefix:
        out["prefix"] = {}
        for i in lay.prefix:
            x, c = run(x, params["prefix"][f"l{i}"],
                       one(caches["prefix"][f"l{i}"]), i)
            out["prefix"][f"l{i}"] = jax.tree.map(lambda a: a[0], c)
    stacked = lay.n_units > 1
    units = []
    for u in range(lay.n_units):
        pick = (lambda a: a[u]) if stacked else (lambda a: a)
        up = jax.tree.map(pick, params["units"])
        uc = {}
        for r in range(lay.unit_len):
            x, c = run(x, up[f"r{r}"],
                       one(jax.tree.map(pick, caches["units"][f"r{r}"])),
                       lay.prefix_len + r)
            uc[f"r{r}"] = jax.tree.map(lambda a: a[0], c)
        units.append(uc)
    out["units"] = (jax.tree.map(lambda *xs: jnp.stack(xs), *units)
                    if stacked else units[0])
    return layers.apply_norm(cfg, params["final_norm"], x), out


def _decode_loop(cfg):
    def step(params, caches, tokens, cur_len, page_table):
        def run(xx, bp, c, idx):
            return blocks.block_paged_decode(cfg, bp, xx, c, 0, cur_len,
                                             page_table, idx)

        x = layers.embed_tokens(cfg, params["embed"], tokens)
        x, caches = _per_layer(cfg, params, caches, x, run)
        return layers.logits_from_hidden(cfg, params["embed"], x)[:, 0], \
            caches
    return step


def _chunk_loop(cfg):
    def step(params, caches, tokens, offset, last_idx, page_table):
        valid_len = jnp.maximum(last_idx + 1, 0)

        def run(xx, bp, c, idx):
            return blocks.block_paged_prefill_chunk(
                cfg, bp, xx, c, 0, offset, valid_len, page_table, idx)

        x = layers.embed_tokens(cfg, params["embed"], tokens)
        x, caches = _per_layer(cfg, params, caches, x, run)
        i = jnp.clip(last_idx, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, i[:, None, None], axis=1)
        return layers.logits_from_hidden(cfg, params["embed"],
                                         x_last)[:, 0], caches
    return step


def _assert_same(got, want):
    (logits, pools), (ref_logits, ref_pools) = got, want
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert jax.tree.structure(pools) == jax.tree.structure(ref_pools)
    for a, b in zip(jax.tree.leaves(pools), jax.tree.leaves(ref_pools)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_decode_matches_per_layer_loop(case):
    cfg, params, pools, pt = _setup(case)
    tokens = jnp.asarray([[5], [17], [3]], jnp.int32)
    cur = jnp.asarray([0, 9, 21], jnp.int32)
    step = jax.jit(M.make_paged_serve_fns(cfg).decode)
    got = step(params, pools, tokens, cur, pt)
    want = jax.jit(_decode_loop(cfg))(params, pools, tokens, cur, pt)
    _assert_same(got, want)


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_chunk_matches_per_layer_loop(case):
    cfg, params, pools, pt = _setup(case)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab_size, (B, T)), jnp.int32)
    offset = jnp.asarray([0, 12, 4], jnp.int32)
    last_idx = jnp.asarray([T - 1, 2, -1], jnp.int32)   # full, part, idle
    step = jax.jit(M.make_paged_serve_fns(cfg).prefill_chunk)
    got = step(params, pools, tokens, offset, last_idx, pt)
    want = jax.jit(_chunk_loop(cfg))(params, pools, tokens, offset,
                                     last_idx, pt)
    _assert_same(got, want)
