"""CPU rehearsal of chip_smoke.py: its phases at reduced size.

The smoke test itself only runs on a TPU.  Here every phase runs on a
reduced qwen3-0.6b with the Pallas kernels in interpret mode (steered
by the dispatch variables, as on a chip "auto" would pick the compiled
kernels), and ``main()`` must refuse the CPU.  The kernel-family check
is exercised on a compiled TPU program in tests/test_tpu_compile.py.
"""
import importlib.util
import pathlib

import jax.numpy as jnp
import pytest

from repro import configs

ROOT = pathlib.Path(__file__).resolve().parents[1]
LAYOUTS = ["contiguous", "paged"]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def interpret(monkeypatch, smoke):
    for name in smoke.LAX_DISPATCH:
        monkeypatch.setenv(name, "pallas_interpret")
    monkeypatch.setenv("PMT_DECODE_ATTN_IMPL", "flash")


@pytest.fixture(scope="module")
def model(smoke):
    cfg = configs.get_config("qwen3-0.6b", reduced=True)
    # three prompts of 2-3 prefill chunks each
    return cfg, smoke.init_params(cfg), smoke.make_prompts(cfg, n=3,
                                                           lens=(40, 90))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_logit_phase_pallas_matches_lax(smoke, model, interpret, layout):
    cfg, params, prompts = model
    gap, agree, text = smoke.logit_phase(cfg, params, prompts, layout,
                                         max_len=128)
    assert gap <= smoke.LOGIT_BOUND
    assert agree == len(prompts)
    assert smoke.kernel_families(text) == set()     # nothing for a TPU here


def test_logit_phase_fails_on_a_wrong_kernel(smoke, model, interpret,
                                             monkeypatch):
    from repro.kernels.prefill_attention import ops

    monkeypatch.setattr(ops, "prefill_attention_pallas",
                        lambda q, *a, **k: jnp.zeros_like(q))
    cfg, params, prompts = model
    with pytest.raises(smoke.SmokeFailure, match="differ by"):
        smoke.logit_phase(cfg, params, prompts, "contiguous", max_len=128)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_serve_phase_prices_every_request(smoke, model, interpret, layout):
    cfg, params, prompts = model
    res = smoke.serve_phase(cfg, params, prompts, layout, batch=2,
                            max_len=128, max_new=4)
    assert res["tokens"] == 4 * len(prompts)
    assert [r.finish_reason for r, _ in res["requests"]] == \
        ["length"] * len(prompts)
    assert all(j > 0 for _, j in res["requests"])
    assert res["stats"]["kv_layout"] == layout


def test_check_kernels_names_the_missing_family(smoke):
    line = ('%k = bf16[8] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(decode_fn)/while/'
            'body/decode_attention/pallas_call"}')
    assert smoke.kernel_families(line) == {"decode_attention"}
    with pytest.raises(smoke.SmokeFailure, match="cache_update"):
        smoke.check_kernels(line, "contiguous", "decode")


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    without it the cache is the checkout's gitignored ``.jax_cache``."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
        assert enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
        assert enable_compile_cache() == str(tmp_path / env)
        assert calls == []
    assert jax.config.jax_compilation_cache_dir == before


def test_main_refuses_a_cpu(smoke, capsys):
    assert smoke.main() == 1
    out = capsys.readouterr()
    assert "'cpu'" in out.err
    assert out.out == ""
