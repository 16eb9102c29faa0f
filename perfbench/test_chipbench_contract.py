"""BENCHMARK.json and the result line against the benchmark's contract."""
import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import run_tiny

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    assert len(BENCH["command"]) <= 32
    assert all(_line_ok(w) and not w.startswith("/")
               for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int) \
        and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[g]]
    assert all(NAME.match(n) for n in names)
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[g]}) == len(BENCH[g])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_configs_and_cells():
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    assert 1 <= len(cfgs) <= 24 and 1 <= len(BENCH["workloads"]) <= 24
    files = set()
    for c in cfgs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"] or \
            body["source"].startswith(c["source"])
        assert c["reduced"] == body["reduced"] and len(c["reduced"]) <= 16
        assert _line_ok(c["why"]) and _line_ok(c["source"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and _line_ok(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(cfgs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line_ok(m["layer"])
        assert m["moves"] in e2e
        assert (HERE / "metrics" / f"{m['name']}.py").exists()
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in cells:
        rep = [m for m in BENCH["end_to_end"]
               if w in m.get("workloads", cells)]
        assert len(rep) >= 2 and any(m["name"] == "setup_s" for m in rep)
        assert any(w in m.get("workloads", cells)
                   for m in BENCH["per_layer"])


def test_a_full_check_fits_its_time_with_24_cells():
    t = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (t + 60) + 24 * 2 * 90 + 1200 <= 43200


def _check_line(res, cell, group):
    line = json.loads(json.dumps(res, allow_nan=False))
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert list(line)[-1] == "checks"
    assert isinstance(line["correct"], bool)
    want = {m["name"] for m in harness.metrics_for(BENCH, cell, group)}
    assert set(line["metrics"]) <= want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and math.isfinite(m["value"])
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in line["device"]
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    return line, want


def test_result_line(tiny_root, capsys):
    res = run_tiny(tiny_root, "olmo-1b.batch", seed=2**31 + 11)
    line, want = _check_line(res, "olmo-1b.batch", "end_to_end")
    assert set(line["metrics"]) == want
    harness.emit(res)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_traced_result_line(tiny_root):
    res = run_tiny(tiny_root, "qwen3-0.6b.chat", seed=5, trace=True)
    line, _ = _check_line(res, "qwen3-0.6b.chat", "per_layer")
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert {"queue_wait_p90_ms", "prefill_stall_p95_ms",
            "prefix_hit_share"} <= set(line["metrics"])
    # a CPU run has no device plane: no device metric is reported
    assert "device_idle_share" not in line["metrics"]
    for k in ("device_ops", "idle_gaps"):
        assert len(line["breakdown"][k]) <= 10


def _run_cli(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_accelerator_no_result():
    p = _run_cli(ROOT, {})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_tpot_reads_token_times_not_the_cut():
    """A request cut mid-decode counts the tokens it was served, up to
    the last that reached the host; the cut itself adds nothing."""
    cut_late = harness.Served(rid=0, due=0.0, prompt_len=8, served=3,
                              first=1.0, times=[(1, 1.0), (3, 1.2)])
    finished = harness.Served(rid=1, due=0.0, prompt_len=8, served=5,
                              first=1.0, times=[(1, 1.0), (2, 1.1),
                                                (5, 1.4)])
    one = harness.Served(rid=2, due=0.0, prompt_len=8, served=1,
                         first=1.0, times=[(1, 1.0)])
    run = harness.Run(config={},
                      family=harness.family(HERE, {"reference": "dense"}),
                      chunk=32, t0=0.0, window_s=2.0,
                      requests=[cut_late, finished, one], lead_in=[],
                      stats0={}, stats1={}, stall_events=[], peaks={})
    assert harness.end_to_end("tpot_p90_ms", run) == pytest.approx(100.0)
    assert harness.end_to_end("out_tok_per_s", run) == pytest.approx(4.5)


def test_tokens_note_when_they_arrive():
    import program
    r = program.TimedRequest(prompt=[1, 2], max_new_tokens=4)
    r.out = []                          # as the engine does at admission
    r.out.append(7)
    r.out.extend([8, 9])
    assert r.out == [7, 8, 9] and [n for n, _ in r.out.times] == [1, 3]
    assert r.out.times[0][1] <= r.out.times[1][1]
