"""The readers of the engine's phase and request annotations and of the
measurement plane's counters, on hand-built traces and runs whose
answers are known; each reads nothing (None) where its data is
missing, as on a program without the annotations or counters."""
import pytest

import harness
import program
import xplane
from conftest import run_tiny
from xplane import Module, Op, Trace

HERE = harness.HERE


def _read(name, run):
    return harness.reader(HERE, name)(run)


def _run(trace=None, requests=(), stats0=None, stats1=None, window=1e-6):
    return harness.Run(config={},
                       family=harness.family(HERE, {"reference": "dense"}),
                       chunk=32, t0=0.0, window_s=1.0,
                       requests=list(requests), lead_in=[],
                       stats0=stats0 or {}, stats1=stats1 or {},
                       stall_events=[], peaks={}, trace=trace,
                       trace_window_s=window)


def _trace(host):
    # chip 0: a decode module [0, 100) (ops [0, 60) and [70, 100)),
    # idle [100, 150), a prefill module [150, 300), idle [300, 350),
    # an op at [350, 400).
    ops = [Op("paged_decode_attention", 0, 30), Op("fusion", 20, 40),
           Op("fusion", 70, 30), Op("paged_prefill_attention", 150, 50),
           Op("convolution", 200, 100), Op("fusion", 350, 50)]
    mods = [Module("jit_serve_decode", 0, 100),
            Module("jit_serve_prefill_chunk", 150, 150),
            Module("jit_other", 350, 50)]
    tr = Trace(1, [ops], [mods], host)
    xplane.assign_ops(tr, {"paged_decode_attention": "decode",
                           "paged_prefill_attention": "prefill_chunk"})
    return tr


def test_host_bound_idle_share_leaves_out_the_wait():
    # decode [90, 160) covers the first gap's 50 ns, its fetch nested
    # in it adds nothing; the wait covers the second gap; an admit
    # [340, 360) covers 10 ns of it.
    tr = _trace([("engine/decode", 90, 70), ("engine/decode/fetch", 100, 20),
                 ("engine/wait", 300, 50), ("engine/admit", 340, 20),
                 ("PjitFunction(serve_decode)", 100, 50)])
    assert _read("host_bound_idle_share", _run(tr)) == pytest.approx(6.0)


def test_host_bound_idle_share_needs_phase_annotations():
    tr = _trace([("engine/wait", 100, 250),
                 ("PjitFunction(wrapper)", 100, 50)])
    assert _read("host_bound_idle_share", _run(tr)) is None
    assert _read("host_bound_idle_share", _run(None)) is None
    assert _read("host_bound_idle_share",
                 _run(Trace(0, [], [], [("engine/admit", 0, 9)]))) is None


def _served(rid, first=1.0):
    return harness.Served(rid=rid, due=0.0, prompt_len=64, served=3,
                          first=first)


def test_prefill_behind_decode_share():
    # req3's prefill [50, 200) holds 10 + 30 ns of decode ops; req4
    # never got a token and req9 is not a window request.
    tr = _trace([("serve/req3/prefill", 50, 150), ("serve/req3", 50, 300),
                 ("serve/req4/prefill", 0, 100),
                 ("serve/req9/prefill", 0, 100)])
    run = _run(tr, requests=[_served(3), _served(4, first=None)])
    assert _read("prefill_behind_decode_share", run) == pytest.approx(
        100.0 * 40 / 150)


def test_prefill_behind_decode_share_needs_request_annotations():
    tr = _trace([("serve/req3", 50, 300)])
    assert _read("prefill_behind_decode_share",
                 _run(tr, requests=[_served(3)])) is None
    assert _read("prefill_behind_decode_share",
                 _run(None, requests=[_served(3)])) is None


def test_decode_occupancy():
    s0 = {"batch_slots": 4, "decode_steps": 10, "decode_row_steps": 20}
    s1 = {"batch_slots": 4, "decode_steps": 30, "decode_row_steps": 80}
    assert _read("decode_occupancy", _run(stats0=s0, stats1=s1)) \
        == pytest.approx(75.0)
    assert _read("decode_occupancy", _run(stats0=s0, stats1=s0)) is None
    assert _read("decode_occupancy",
                 _run(stats0={"batch_slots": 4},
                      stats1={"batch_slots": 4})) is None


def test_plane_host_share():
    """The counters' change over the time between their own readings,
    which bracket the profiler's start and stop and so run longer than
    the traced window."""
    m0 = {"region_s": 0.1, "sampler_s": 1.0, "resolver_s": 0.2,
          "sampler_ticks": 5, "t_s": 100.0}
    m1 = {"region_s": 0.2, "sampler_s": 1.5, "resolver_s": 0.4,
          "sampler_ticks": 500, "t_s": 150.0}
    run = _run(stats0={"measurement": m0}, stats1={"measurement": m1},
               window=40.0)
    assert _read("plane_host_share", run) == pytest.approx(1.6)
    assert _read("plane_host_share", _run(stats0={}, stats1={},
                                          window=40.0)) is None
    no_clock = [{k: v for k, v in m.items() if k != "t_s"}
                for m in (m0, m1)]
    assert _read("plane_host_share",
                 _run(stats0={"measurement": no_clock[0]},
                      stats1={"measurement": no_clock[1]},
                      window=40.0)) is None
    assert _read("plane_host_share",
                 _run(stats0={"measurement": m0},
                      stats1={"measurement": m0}, window=40.0)) is None


def test_step_program_names_label_no_kernel():
    kernels = list(program.KERNELS.values())
    for name in ("serve_decode", "serve_prefill_chunk", "serve_prefill"):
        assert not any(k in name for k in kernels)
        assert xplane.op_label(
            "fusion.7", {"tf_op": f"jit({name})/while/body/dot_general"},
            kernels) == "fusion"
    assert xplane.op_label(
        "custom-call.2",
        {"tf_op": "jit(serve_decode)/paged_decode_attention/pallas_call"},
        kernels) == "paged_decode_attention"


def test_traced_tiny_run_reports_the_counters(tiny_root):
    """On the CPU the counters reach the result line through
    ``engine.stats()``; with no device plane the trace readers read
    nothing."""
    res = run_tiny(tiny_root, "olmo-1b.batch", seed=2**31 + 5, trace=True)
    m = res["metrics"]
    assert 0.0 < m["decode_occupancy"]["value"] <= 100.0
    assert 0.0 < m["plane_host_share"]["value"] < 100.0
    assert "host_bound_idle_share" not in m
