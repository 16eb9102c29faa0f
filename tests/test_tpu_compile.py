"""Compile the serve kernels for a described TPU v5e, without a chip.

Interpret mode does not enforce the TPU's block tiling or its VMEM
limit; the TPU compiler, which is installed here, does.  Every test
compiles for one device of a ``v5e:2x2`` topology described in a
fixture (never at import: only one process may load the TPU library,
and each test worker imports every test file) and asserts that the
compiled program calls the kernel (``tpu_custom_call``).  Widths are
qwen3-0.6b's (KVH=8, G=2, hd=128) unless a case says otherwise.
"""
import dataclasses
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.cache_update.cache_update import (
    cache_update_pallas, paged_cache_update_pallas, quant_cache_update_pallas,
    quant_paged_cache_update_pallas)
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_paged_pallas, decode_attention_pallas)
from repro.kernels.prefill_attention.prefill_attention import (
    prefill_attention_paged_pallas, prefill_attention_pallas)
from repro.models import model as model_mod
from repro.serve import engine as engine_mod

B, C, T, PS = 8, 1024, 32, 16
NB = C // PS
P = B * NB + 1
L = 3                               # layers in a stacked paged pool
I32, BF16, F32, I8 = jnp.int32, jnp.bfloat16, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _text(one_chip, fn, *shapes):
    args = [jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), s) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _attn_case(kvh, g, hd, dtype=BF16, hdv=None):
    return dict(kvh=kvh, g=g, hd=hd, dtype=dtype, hdv=hdv)


ATTN = {
    "qwen3-bf16": _attn_case(8, 2, 128),
    "qwen3-int8": _attn_case(8, 2, 128, I8),
    "smollm-bf16": _attn_case(3, 3, 64),
    "mla-latent-576": _attn_case(1, 16, 576, hdv=512),
}


def _scales(c, dtype):
    return [S(c[:-1], F32)] * 2 if dtype == I8 else []


@pytest.mark.parametrize("case", ["qwen3-bf16", "qwen3-int8", "smollm-bf16",
                                  "mla-latent-576"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_decode_attention_compiles(one_chip, case, layout):
    a = ATTN[case]
    kvh, g, hd, dt = a["kvh"], a["g"], a["hd"], a["dtype"]
    kw = {"v_width": a["hdv"]} if a["hdv"] else {}
    q = S((B, kvh, g, hd), BF16)
    if layout == "contiguous":
        kv = S((B, C, kvh, hd), dt)
        sc = _scales(kv.shape, dt)
        fn = lambda q, k, v, l, *s: decode_attention_pallas(
            q, k, v, l, k_scale=s[0] if s else None,
            v_scale=s[1] if s else None, **kw)
        text = _text(one_chip, fn, q, kv, kv, S((B,), I32), *sc)
    else:
        kv = S((L, P, PS, kvh, hd), dt)
        sc = _scales(kv.shape, dt)
        fn = lambda q, k, v, i, pt, l, *s: decode_attention_paged_pallas(
            q, k, v, i, pt, l, k_scale=s[0] if s else None,
            v_scale=s[1] if s else None, **kw)
        text = _text(one_chip, fn, q, kv, kv, S((), I32), S((B, NB), I32),
                     S((B,), I32), *sc)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case", ["qwen3-bf16", "qwen3-int8",
                                  "mla-latent-576"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_prefill_attention_compiles(one_chip, case, layout):
    a = ATTN[case]
    kvh, g, hd, dt = a["kvh"], a["g"], a["hd"], a["dtype"]
    kw = {"v_width": a["hdv"]} if a["hdv"] else {}
    q = S((B, kvh, T, g, hd), BF16)
    x = S((B, T, kvh, hd), BF16)
    if layout == "contiguous":
        kv = S((B, C, kvh, hd), dt)
        sc = _scales(kv.shape, dt)
        fn = lambda q, kx, vx, kc, vc, o, *s: prefill_attention_pallas(
            q, kx, vx, kc, vc, o, k_scale=s[0] if s else None,
            v_scale=s[1] if s else None, **kw)
        text = _text(one_chip, fn, q, x, x, kv, kv, S((B,), I32), *sc)
    else:
        kv = S((L, P, PS, kvh, hd), dt)
        sc = _scales(kv.shape, dt)
        fn = lambda q, kx, vx, kc, vc, i, pt, o, *s: \
            prefill_attention_paged_pallas(
                q, kx, vx, kc, vc, i, pt, o, k_scale=s[0] if s else None,
                v_scale=s[1] if s else None, **kw)
        text = _text(one_chip, fn, q, x, x, kv, kv, S((), I32),
                     S((B, NB), I32), S((B,), I32), *sc)
    assert "tpu_custom_call" in text


# Row-block (KVH, hd) and aligned-block (one trailing dim) cache forms.
ROWS = {"kv-bf16": ((8, 128), BF16), "mla-latent": ((576,), BF16),
        "scales-f32": ((8,), F32)}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("t", [0, 1, T])       # 0: contiguous decode write
def test_cache_update_compiles(one_chip, rows, t):
    rest, dt = ROWS[rows]
    if t == 0:
        text = _text(one_chip, cache_update_pallas, S((B, C) + rest, dt),
                     S((B, 1) + rest, dt), S((B,), I32))
    else:
        text = _text(one_chip, paged_cache_update_pallas,
                     S((L, P, PS) + rest, dt), S((), I32),
                     S((B, t) + rest, dt), S((B, NB), I32), S((B,), I32),
                     S((B,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["int8", "fp8_e4m3"])
@pytest.mark.parametrize("t", [0, 1, T])
def test_quant_cache_update_compiles(one_chip, mode, t):
    codes = I8 if mode == "int8" else jnp.float8_e4m3fn
    if t == 0:
        fn = lambda c, s, n, sl: quant_cache_update_pallas(c, s, n, sl, mode)
        text = _text(one_chip, fn, S((B, C, 8, 128), codes),
                     S((B, C, 8), F32), S((B, 1, 8, 128), BF16),
                     S((B,), I32))
    else:
        fn = lambda c, s, i, n, pt, st, va: quant_paged_cache_update_pallas(
            c, s, i, n, pt, st, va, mode)
        text = _text(one_chip, fn, S((L, P, PS, 8, 128), codes),
                     S((L, P, PS, 8), F32), S((), I32),
                     S((B, t, 8, 128), BF16), S((B, NB), I32), S((B,), I32),
                     S((B,), I32))
    assert "tpu_custom_call" in text


@pytest.fixture
def pallas_dispatch(monkeypatch):
    """Off the TPU "auto" picks the lax twins: steer the serve steps to
    the compiled kernels, as "auto" does on a chip."""
    for name in ("PMT_PREFILL_ATTENTION_DISPATCH",
                 "PMT_DECODE_ATTENTION_DISPATCH", "PMT_CACHE_UPDATE_IMPL"):
        monkeypatch.setenv(name, "pallas")
    monkeypatch.setenv("PMT_DECODE_ATTN_IMPL", "flash")


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_serve_steps_hold_every_kernel(one_chip, pallas_dispatch, layout):
    """The chip smoke test's kernel check, on the compiled serve steps of
    a reduced qwen3-0.6b (2 layers; head widths 16) for the chip."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", pathlib.Path(__file__).resolve().parents[1]
        / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = configs.get_config("qwen3-0.6b", reduced=True)
    params = jax.eval_shape(
        lambda: model_mod.init_params(jax.random.PRNGKey(0), cfg)[0])
    toks, rows = S((B, 1), I32), S((B,), I32)
    if layout == "contiguous":
        caches = jax.eval_shape(lambda: model_mod.init_caches(cfg, B, C))
        decode = _text(one_chip, engine_mod.make_decode_fn(cfg), params,
                       caches, toks, rows)
        row = jax.eval_shape(lambda: model_mod.init_caches(cfg, 1, C))
        prefill = _text(one_chip, engine_mod.make_prefill_chunk_fn(cfg),
                        params, row, S((1, T), I32), S((), I32), S((), I32))
    else:
        pools = jax.eval_shape(
            lambda: model_mod.init_paged_caches(cfg, P, PS))
        decode = _text(one_chip, engine_mod.make_paged_decode_fn(cfg),
                       params, pools, toks, rows, S((B, NB), I32))
        prefill = _text(one_chip,
                        engine_mod.make_paged_prefill_chunk_fn(cfg), params,
                        pools, S((B, T), I32), rows, rows, S((B, NB), I32))
    assert smoke.check_kernels(decode, layout, "decode") == \
        smoke.KERNELS[(layout, "decode")]
    assert smoke.check_kernels(prefill, layout, "prefill") == \
        smoke.KERNELS[(layout, "prefill")]


# A pool larger than the chip's VMEM, as a deployed pool is: the
# compiler is free to stage a small one through VMEM with copies of its
# own, which would hide what the guard looks for.
GUARD_PAGES = 4097


# The archs at their published widths, cut in depth so that the units
# run under the layer scan: 2 layers, or DeepSeek-V3's 3 dense layers
# and 2 MoE layers (8 experts held, one eighth of the vocabulary), with
# the pages that make one layer's pool larger than the chip's VMEM.
POOL_CUTS = {"qwen3-0.6b": (dict(num_layers=2), GUARD_PAGES),
             "olmo-1b": (dict(num_layers=2), GUARD_PAGES),
             "deepseek-v3-671b": (dict(num_layers=5, vocab_size=16160,
                                       mtp=False, moe={"held_experts": 8}),
                                  2 * GUARD_PAGES - 1)}


@pytest.mark.parametrize("arch", sorted(POOL_CUTS))
def test_paged_steps_keep_the_pool_in_place(one_chip, pallas_dispatch,
                                            arch):
    """The compiled paged decode and chunk programs at the arch's
    published widths hold no copy, slice or update whose shape is a
    whole layer's pool or the stacked pool, and no temporary buffer as
    large as one layer's pool: the kernels read and write the stacked
    pool in place, aliased from input to output."""
    cut, pages = POOL_CUTS[arch]
    cfg = configs.get_config(arch, param_dtype="bfloat16", **cut)
    params = jax.eval_shape(
        lambda: model_mod.init_params(jax.random.PRNGKey(0), cfg)[0])
    pools = jax.eval_shape(
        lambda: model_mod.init_paged_caches(cfg, pages, PS))
    leaves = jax.tree.leaves(pools["units"])
    prefix = jax.tree.leaves(pools.get("prefix", {}))
    assert all(a.shape[0] == 2 for a in leaves)     # stacked on "layers"
    layer_pool = min(a.size // 2 * a.dtype.itemsize for a in leaves)
    # the stacked pool, one layer's pool (a prefix layer's too), and the
    # stack as one pool of L * P pages, as the kernels see it
    shapes = {tuple(a.shape) for a in leaves + prefix} | \
        {tuple(a.shape[1:]) for a in leaves} | \
        {(a.shape[0] * a.shape[1],) + tuple(a.shape[2:]) for a in leaves}
    rows = S((B,), I32)
    steps = {
        "decode": (engine_mod.make_paged_decode_fn(cfg),
                   (params, pools, S((B, 1), I32), rows, S((B, NB), I32))),
        "prefill": (engine_mod.make_paged_prefill_chunk_fn(cfg),
                    (params, pools, S((B, T), I32), rows, rows,
                     S((B, NB), I32))),
    }
    op = re.compile(r"= \w+\[([0-9,]*)\]\S* ([a-z-]+)\(")
    for name, (fn, shapes_in) in steps.items():
        args = [jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), a) for a in shapes_in]
        compiled = jax.jit(fn, donate_argnums=1).lower(*args).compile()
        text = compiled.as_text()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < layer_pool, (name, temp, layer_pool)
        moved = [(kind, dims) for dims, kind in op.findall(text)
                 if kind in ("copy", "copy-done", "dynamic-slice",
                             "dynamic-update-slice", "fusion", "slice",
                             "concatenate")
                 and tuple(int(d) for d in dims.split(",") if d) in shapes]
        assert moved == [], (name, moved)
        assert "paged_cache_update" in text, name


def test_grouped_experts_compile_over_the_routed_rows(one_chip):
    """Kimi-K2's expert layer at its published widths (384 experts of
    2048, top-8), 16 experts held, over a 64 x 32 prefill chunk under a
    scan of 2 layers: the held experts run as a grouped matmul over the
    routed rows (``moe.GROUPED_MIN_ROWS``), and the program holds no
    temporary buffer as large as one layer's held experts.  (The
    grouped matmul takes each weight of the layer sliced out of the
    stack, one at a time: a copy the batched FFN does not make.)"""
    from repro.models import moe
    cfg = configs.get_config("kimi-k2-1t-a32b", param_dtype="bfloat16",
                             num_layers=2, moe={"held_experts": 16})
    m = cfg.moe
    d, ff, held, layers_ = cfg.d_model, m.ff_dim, m.held, 2
    assert m.top_k < held and B * T >= moe.GROUPED_MIN_ROWS
    stack = {"router": S((layers_, d, m.num_experts), BF16),
             "w_up": S((layers_, held, d, ff), BF16),
             "w_gate": S((layers_, held, d, ff), BF16),
             "w_down": S((layers_, held, ff, d), BF16)}

    def layers_fn(stack, x):
        def one(x, p):
            out, counts = moe.moe_serve(cfg, p, x)
            return x + out, counts
        return jax.lax.scan(one, x, stack)

    compiled = jax.jit(layers_fn).lower(
        *[jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), a)
          for a in (stack, S((B, T, d), BF16))]).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    experts = 3 * held * d * ff * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < experts, (temp, experts)
