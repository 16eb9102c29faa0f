"""A later change adds a configuration, a traffic mix and a per-layer
metric by adding files alone: here from a temporary directory."""
import json

from conftest import run_tiny, tiny_config, tiny_mix

READER = '''"""Dummy: served tokens of the window."""


def read(run):
    return float(sum(r.served for r in run.requests))
'''


def test_files_alone_register_config_mix_and_metric(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    d = tiny_root / bench["paths"][0]
    cfg = tiny_config("qwen3-0.6b")
    cfg.update(name="dummy", num_hidden_layers=3)
    cfg["program"]["overrides"]["num_layers"] = 3
    (d / "configs" / "dummy.json").write_text(json.dumps(cfg))
    mix = tiny_mix("chat")
    mix["output"].update(min=4, max=12, median=8)
    (d / "traffic" / "dummy_mix.json").write_text(json.dumps(mix))
    (d / "metrics" / "dummy_tokens.py").write_text(READER)
    bench["configs"].append({"name": "dummy", "source": cfg["source"],
                             "file": f"{bench['paths'][0]}/configs/"
                                     "dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy.dummy_mix",
                               "config": "dummy", "traffic": "dummy_mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "dummy_tokens", "unit": "tokens",
                               "better": "higher", "source": "program_span",
                               "layer": "test", "moves": "tpot_p90_ms",
                               "workloads": ["dummy.dummy_mix"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_tiny(tiny_root, "dummy.dummy_mix", seed=9, trace=True)
    assert res["correct"] is True
    assert res["metrics"]["dummy_tokens"]["value"] > 0
    assert "queue_wait_p90_ms" not in res["metrics"]
