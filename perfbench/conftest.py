"""Fixtures for the benchmark's CPU tests: a copy of the benchmark with
every configuration cut to a few thousand parameters, run on the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil

import pytest

HERE = pathlib.Path(__file__).resolve().parent


def benchmark(root: pathlib.Path = HERE.parent) -> dict:
    """``BENCHMARK.json`` under ``root``."""
    return json.loads((root / "BENCHMARK.json").read_text())


def tiny_config(name: str, root: pathlib.Path = HERE.parent) -> dict:
    """Config ``name`` of the benchmark under ``root``, cut to the size
    its file's ``tiny`` key gives: the program's reduced config, the
    published-key sizes beside it, and the widest logit gap the tiny
    copy may show, set between what bf16 serving reads against the
    float32 reference and what the control, the reference in fp8,
    reads on the CPU."""
    entry = {c["name"]: c for c in benchmark(root)["configs"]}[name]
    c = json.loads((root / entry["file"]).read_text())
    tiny = c["tiny"]
    c.update(tiny["published"])
    c["program"]["overrides"].update(tiny["program"])
    c["serve"].update(slots=4, max_len=512, pool_pages=160)
    c["correct"]["max_logit_gap"] = tiny["max_logit_gap"]
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
    bench = benchmark()
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
