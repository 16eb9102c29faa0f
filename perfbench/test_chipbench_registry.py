"""A later change adds a configuration (of the dense family or of one it
brings), a traffic mix and a per-layer metric by adding files alone:
here from a temporary directory."""
import json

import flops
import harness
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


TOY_FAMILY = '''"""A test family: the dense family's functions, from the file beside
this one, with twice its matmul operations."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "toy_dense", pathlib.Path(__file__).with_name("dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)

stated, layout = _dense.stated, _dense.layout
head_flops, attn_flops, attn_bytes = (
    _dense.head_flops, _dense.attn_flops, _dense.attn_bytes)


def matmul_flops_per_token(c):
    return 2 * _dense.matmul_flops_per_token(c)
'''


def test_files_alone_register_a_new_family(tiny_root):
    """A config whose ``reference`` names a family other than dense
    runs from its reference, family and config files (with a ``tiny``
    key) added under the root alone, and the run's work counts are the
    new family's."""
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    d = tiny_root / bench["paths"][0]
    assert not (harness.HERE / "families" / "toy.py").exists()
    assert not (harness.HERE / "reference" / "toy.py").exists()
    (d / "families" / "toy.py").write_text(TOY_FAMILY)
    (d / "reference" / "toy.py").write_text(
        (d / "reference" / "dense.py").read_text())
    cfg = json.loads((harness.HERE / "configs" / "qwen3-0.6b.json")
                     .read_text())
    cfg.update(name="toy", reference="toy")
    assert "tiny" in cfg
    path = f"{bench['paths'][0]}/configs/toy.json"
    (tiny_root / path).write_text(json.dumps(cfg))
    bench["configs"].append({"name": "toy", "source": cfg["source"],
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.chat", "config": "toy",
                               "traffic": "chat", "chips": 1,
                               "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (tiny_root / path).write_text(json.dumps(tiny_config("toy", tiny_root)))
    details = {}
    res = run_tiny(tiny_root, "toy.chat", seed=2**31 + 17, details=details)
    assert res["correct"] is True, res["checks"]
    run = details["run"]
    assert run.family.__file__ == str(d / "families" / "toy.py")
    dense = harness.family(d, {"reference": "dense"})
    r = max(run.all_requests, key=lambda r: r.served)
    steps = r.served - 1
    assert steps > 0
    toy_w = flops.decode_work(run.family, run.config, r.prompt_len, r.served)
    dense_w = flops.decode_work(dense, run.config, r.prompt_len, r.served)
    assert toy_w["flops"] - dense_w["flops"] == \
        steps * dense.matmul_flops_per_token(run.config)
    assert toy_w["attn_bytes"] == dense_w["attn_bytes"]
