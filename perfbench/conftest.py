"""Fixtures for the benchmark's CPU tests: a copy of the benchmark with
both configurations cut to a few thousand parameters, run on the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil

import pytest

HERE = pathlib.Path(__file__).resolve().parent

# Sizes of the tiny copies: the program's reduced configs, with the
# benchmark's published-key names beside them.
TINY = {
    "qwen3-0.6b": dict(
        program=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                     d_ff=128, vocab_size=256, head_dim=16),
        published=dict(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16, vocab_size=256)),
    "olmo-1b": dict(
        program=dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                     d_ff=128, vocab_size=256, head_dim=16),
        published=dict(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, head_dim=16, vocab_size=256)),
}

# Widest logit gap each tiny copy may show, set between what bf16
# serving reads against the float32 reference (seeds 1-3: qwen3 0.0029
# at most, olmo 0.014) and what the control, the reference in fp8,
# reads (qwen3 0.099 and more), on the CPU.
TINY_LIMIT = {"qwen3-0.6b": 0.007, "olmo-1b": 0.025}


def tiny_config(name: str) -> dict:
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    c.update(TINY[name]["published"])
    c["program"]["overrides"].update(TINY[name]["program"])
    c["serve"].update(slots=4, max_len=512, pool_pages=160)
    c["correct"]["max_logit_gap"] = TINY_LIMIT[name]
    return c


def tiny_mix(name: str) -> dict:
    m = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    m["engine"].update(slots=4, max_len=512)
    for key in ("prompt", "output"):
        if key in m:
            d = m[key]
            d["max"] = min(d["max"], 96 if key == "output" else 160)
            d["min"] = min(d["min"], d["max"] // 2)
            if "median" in d:
                d["median"] = min(d["median"], (d["min"] + d["max"]) // 2)
    m.update({k: v for k, v in dict(rate_per_s=3.0, count=10,
                                    warm_s=0.5).items() if k in m})
    return m


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark root at ``tmp``: this benchmark's files and
    ``BENCHMARK.json``, with tiny configurations and mixes."""
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    dst = tmp / bench["paths"][0]
    shutil.copytree(HERE, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py", "conftest.py"))
    for c in bench["configs"]:
        (tmp / c["file"]).write_text(json.dumps(tiny_config(c["name"])))
    for w in bench["workloads"]:
        (dst / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(tiny_mix(w["traffic"])))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_tiny(root, cell, seed=123, seconds=1.5, trace=False, **kw):
    import time

    import harness
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.monotonic(), root=root,
                            require_accelerator=False, compile_cache=False,
                            **kw)
