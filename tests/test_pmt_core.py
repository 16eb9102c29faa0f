"""Unit + property tests for the PMT core (the paper's contribution)."""
import math
import os
import threading
import time

import pytest
# Shared strategies package: real hypothesis when installed, a
# deterministic-grid fallback otherwise (see tests/strategies).
from strategies import HAS_HYPOTHESIS, given, settings, st

import repro.core as pmt
from repro.core.sensor import Sample, Sensor, SensorError
from repro.core.state import State


# ---------------------------------------------------------------------------
# State derivations: joules / watts / seconds (paper Listing 1 semantics)
# ---------------------------------------------------------------------------

finite = st.floats(min_value=0.0, max_value=1e12, allow_nan=False,
                   allow_infinity=False)


@given(t0=st.floats(min_value=0.0, max_value=1e6),
       dt=st.floats(min_value=1e-6, max_value=1e6),
       j0=finite, dj=st.floats(min_value=0.0, max_value=1e9))
def test_state_identities(t0, dt, j0, dj):
    a = State(timestamp_s=t0, joules=j0)
    b = State(timestamp_s=t0 + dt, joules=j0 + dj)
    s = pmt.seconds(a, b)
    j = pmt.joules(a, b)
    w = pmt.watts(a, b)
    # abs tolerance covers float cancellation in (t0 + dt) - t0
    assert s == pytest.approx(dt, rel=1e-6, abs=1e-5)
    assert j == pytest.approx(dj, rel=1e-6, abs=1e-3)
    # J = W * s — the fundamental identity the API exposes.
    assert j == pytest.approx(w * s, rel=1e-6, abs=1e-6)


def test_zero_interval_watts_is_zero():
    a = State(timestamp_s=5.0, joules=10.0)
    assert pmt.watts(a, a) == 0.0


def test_negative_joules_rejected():
    with pytest.raises(ValueError):
        State(timestamp_s=0.0, joules=-1.0)


def test_rail_joules():
    a = State(0.0, 0.0, rails={"pkg": 1.0, "dram": 0.5})
    b = State(1.0, 2.0, rails={"pkg": 2.5, "dram": 0.75})
    assert pmt.rail_joules(a, b, "pkg") == pytest.approx(1.5)
    with pytest.raises(KeyError):
        pmt.rail_joules(a, b, "gpu")


# ---------------------------------------------------------------------------
# Sensor base class: power integration for power-only backends
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_power_only_backend_trapezoidal_integration():
    clk = FakeClock()
    s = pmt.create("dummy", watts=100.0, clock=clk)
    a = s.read()
    clk.advance(2.0)
    b = s.read()
    # constant 100 W over 2 s -> 200 J
    assert pmt.joules(a, b) == pytest.approx(200.0)
    assert pmt.watts(a, b) == pytest.approx(100.0)


def test_waveform_backend_trapezoid_matches_analytic():
    clk = FakeClock()
    # ramp 0 -> 100 W over 1 s: trapezoid with samples at 0 and 1 gives 50 J
    s = pmt.create("dummy", watts_fn=lambda t: 100.0 * t, clock=clk)
    a = s.read()
    clk.advance(1.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(50.0)


def test_sensor_requires_some_reading():
    class Bad(Sensor):
        name = "bad"

        def _sample(self):
            return Sample()

    with pytest.raises(SensorError):
        Bad().read()


def test_monotone_joules_under_many_reads():
    clk = FakeClock()
    s = pmt.create("dummy", watts=7.0, clock=clk)
    last = s.read()
    for _ in range(50):
        clk.advance(0.01)
        cur = s.read()
        assert cur.joules >= last.joules
        last = cur


# ---------------------------------------------------------------------------
# Registry (paper: extensible back ends)
# ---------------------------------------------------------------------------

def test_registry_contains_paper_backends():
    names = pmt.backend_names()
    for expected in ["rapl", "sysfs", "nvml", "cpuutil", "tpu", "dummy"]:
        assert expected in names


def test_registry_unknown_backend():
    with pytest.raises(KeyError):
        pmt.create("powersensor99")


def test_registry_extension_point():
    class MySensor(Sensor):
        name = "custom"

        def _sample(self):
            return Sample(watts=1.0)

    pmt.register_backend("custom", MySensor)
    try:
        s = pmt.create("custom")
        assert isinstance(s, MySensor)
    finally:
        # keep global registry clean for other tests
        from repro.core import registry
        registry._REGISTRY.pop("custom", None)


# ---------------------------------------------------------------------------
# RAPL backend against a fixture powercap tree (incl. wraparound)
# ---------------------------------------------------------------------------

def _write(path, content):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(str(content))


def make_rapl_tree(root, packages=2, energy_uj=1000000, max_range=10000000):
    for i in range(packages):
        zone = os.path.join(root, f"intel-rapl:{i}")
        _write(os.path.join(zone, "name"), f"package-{i}")
        _write(os.path.join(zone, "energy_uj"), energy_uj)
        _write(os.path.join(zone, "max_energy_range_uj"), max_range)
        # one subzone (must NOT be double counted in the total)
        sub = os.path.join(root, f"intel-rapl:{i}:0")
        _write(os.path.join(sub, "name"), "core")
        _write(os.path.join(sub, "energy_uj"), energy_uj // 2)
        _write(os.path.join(sub, "max_energy_range_uj"), max_range)


def test_rapl_fixture_tree(tmp_path):
    root = str(tmp_path / "powercap")
    make_rapl_tree(root, packages=2, energy_uj=1_000_000)
    clk = FakeClock()
    s = pmt.create("rapl", root=root, clock=clk)
    assert s.kind == "measured"
    a = s.read()
    # both packages advance by 0.5 J (500000 uJ); subzones by 0.25 J
    for i in range(2):
        _write(os.path.join(root, f"intel-rapl:{i}", "energy_uj"), 1_500_000)
        _write(os.path.join(root, f"intel-rapl:{i}:0", "energy_uj"), 750_000)
    clk.advance(1.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(1.0)  # 2 packages x 0.5 J
    assert pmt.watts(a, b) == pytest.approx(1.0)
    assert pmt.rail_joules(a, b, "intel-rapl:0:0:core") == pytest.approx(0.25)


def test_rapl_wraparound(tmp_path):
    root = str(tmp_path / "powercap")
    make_rapl_tree(root, packages=1, energy_uj=9_900_000, max_range=10_000_000)
    clk = FakeClock()
    s = pmt.create("rapl", root=root, clock=clk)
    a = s.read()
    # counter wraps: 9.9e6 -> 0.1e6 over max_range 1e7 => +0.2 J consumed
    _write(os.path.join(root, "intel-rapl:0", "energy_uj"), 100_000)
    _write(os.path.join(root, "intel-rapl:0:0", "energy_uj"), 100_000)
    clk.advance(1.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(0.2)


def test_rapl_unavailable_without_tree(tmp_path):
    with pytest.raises(SensorError):
        pmt.create("rapl", root=str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# sysfs backend against a fixture hwmon tree
# ---------------------------------------------------------------------------

def test_sysfs_power_files(tmp_path):
    p1 = str(tmp_path / "hwmon0" / "power1_input")
    p2 = str(tmp_path / "hwmon1" / "power1_input")
    _write(p1, 25_000_000)  # 25 W in uW
    _write(p2, 10_000_000)  # 10 W
    clk = FakeClock()
    s = pmt.create("sysfs", files=[p1, p2], clock=clk)
    a = s.read()
    clk.advance(2.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(70.0)  # 35 W x 2 s
    assert b.watts == pytest.approx(35.0)


def test_sysfs_energy_files(tmp_path):
    e = str(tmp_path / "hwmon0" / "energy1_input")
    _write(e, 1_000_000)  # 1 J in uJ
    clk = FakeClock()
    s = pmt.create("sysfs", files=[e], clock=clk)
    a = s.read()
    _write(e, 4_000_000)
    clk.advance(1.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(3.0)


def test_sysfs_rejects_unknown_file(tmp_path):
    f = str(tmp_path / "hwmon0" / "temp1_input")
    _write(f, 42)
    with pytest.raises(SensorError):
        pmt.create("sysfs", files=[f])


# ---------------------------------------------------------------------------
# cpuutil backend against fixture /proc/stat
# ---------------------------------------------------------------------------

def make_proc(tmp_path, busy, idle):
    # user nice system idle iowait irq softirq steal
    _write(str(tmp_path / "proc" / "stat"),
           f"cpu {busy} 0 0 {idle} 0 0 0 0 0 0\n")
    return str(tmp_path / "proc")


def test_cpuutil_utilization_model(tmp_path):
    procfs = make_proc(tmp_path, busy=100, idle=900)
    clk = FakeClock()
    s = pmt.create("cpuutil", tdp_w=110.0, idle_w=10.0, procfs=procfs,
                   clock=clk)
    s.read()
    # now 50% utilization over the delta: +100 busy, +100 idle
    make_proc(tmp_path, busy=200, idle=1000)
    clk.advance(1.0)
    b = s.read()
    # P = 10 + (110-10)*0.5 = 60 W
    assert b.watts == pytest.approx(60.0)
    assert s.kind == "hybrid"


def test_cpuutil_clamps_utilization(tmp_path):
    procfs = make_proc(tmp_path, busy=100, idle=900)
    s = pmt.create("cpuutil", procfs=procfs, clock=FakeClock())
    s.read()
    make_proc(tmp_path, busy=90, idle=900)  # counter went backwards
    assert 0.0 <= s.utilization() <= 1.0


# ---------------------------------------------------------------------------
# TPU cost-model backend (the TPU-native adaptation)
# ---------------------------------------------------------------------------

def test_tpu_sensor_idle_floor():
    clk = FakeClock()
    s = pmt.create("tpu", chips=2, clock=clk)
    a = s.read()
    clk.advance(10.0)
    b = s.read()
    # idle 60 W x 2 chips x 10 s
    assert pmt.joules(a, b) == pytest.approx(1200.0)
    assert s.kind == "modeled"


def test_tpu_sensor_accounts_dynamic_energy():
    clk = FakeClock()
    s = pmt.create("tpu", chips=1, clock=clk)
    a = s.read()
    dyn = s.account(flops=1e12, hbm_bytes=0.0, ici_bytes=0.0, seconds=1.0)
    # 1e12 FLOP x 0.55 pJ = 0.55 J of dynamic energy
    assert dyn == pytest.approx(0.55)
    clk.advance(1.0)
    b = s.read()
    assert pmt.joules(a, b) == pytest.approx(60.0 + 0.55)


def test_tpu_sensor_power_cap():
    s = pmt.create("tpu", chips=1, clock=FakeClock())
    # absurd FLOPs in 1 s must be capped at (peak - idle) x 1 s
    dyn = s.account(flops=1e20, hbm_bytes=0, ici_bytes=0, seconds=1.0)
    assert dyn == pytest.approx(200.0 - 60.0)


@pytest.mark.parametrize("kind,expect", [("TPU v5 lite", "tpu-v5e"),
                                         ("TPU v9 imaginary", None)])
def test_tpu_sensor_peaks_by_device_kind(monkeypatch, kind, expect):
    """On a TPU backend the modelled sensor takes its peaks from the
    device kind; an unknown kind raises instead of modelling v5e."""
    import jax

    class _Dev:
        device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    if expect is None:
        with pytest.raises(ValueError, match="no HardwareSpec"):
            pmt.create("tpu")
    else:
        assert pmt.create("tpu").model.hw.name == expect
    # an explicit model is used as given, whatever the device
    m = pmt.EnergyModel()
    assert pmt.create("tpu", model=m).model is m


@given(flops=st.floats(0, 1e18), hbm=st.floats(0, 1e15),
       ici=st.floats(0, 1e15), secs=st.floats(1e-3, 1e3))
@settings(max_examples=50, deadline=None)
def test_energy_model_properties(flops, hbm, ici, secs):
    m = pmt.EnergyModel()
    e = m.step_joules(flops, hbm, ici, secs)
    # never below the idle floor, never above the board envelope
    assert e >= m.static_joules(secs) - 1e-9
    assert e <= m.hw.peak_w * secs + 1e-6
    # monotone in each activity term (pre-cap region check via dynamic)
    assert m.dynamic_joules(flops + 1e9, hbm, ici) >= m.dynamic_joules(
        flops, hbm, ici)


# ---------------------------------------------------------------------------
# Dump mode (paper mode 1)
# ---------------------------------------------------------------------------

def test_dump_mode_roundtrip(tmp_path):
    path = str(tmp_path / "trace.pmt")
    s = pmt.create("dummy", watts=20.0)
    s.start_dump_thread(path, period_s=0.005)
    time.sleep(0.06)
    s.stop_dump_thread()
    hdr, recs = pmt.read_dump(path)
    assert hdr.sensor == "dummy" and hdr.kind == "modeled"
    assert len(recs) >= 3
    assert pmt.average_watts(recs) == pytest.approx(20.0, rel=0.05)
    # timestamps strictly non-decreasing, joules non-decreasing
    for r0, r1 in zip(recs, recs[1:]):
        assert r1.t_rel_s >= r0.t_rel_s
        assert r1.joules >= r0.joules


def test_dump_thread_double_start_rejected(tmp_path):
    s = pmt.create("dummy")
    s.start_dump_thread(str(tmp_path / "a.pmt"))
    try:
        with pytest.raises(SensorError):
            s.start_dump_thread(str(tmp_path / "b.pmt"))
    finally:
        s.stop_dump_thread()


def test_dump_reader_rejects_garbage(tmp_path):
    p = tmp_path / "bad.pmt"
    p.write_text("hello world\n1 2 3\n")
    with pytest.raises(ValueError):
        pmt.read_dump(str(p))


def test_period_clamped_to_native(tmp_path):
    from repro.core.sampler import clamp_period
    s = pmt.create("dummy")  # native 1 ms
    assert clamp_period(s, None) == s.native_period_s
    assert clamp_period(s, 1e-9) == s.native_period_s
    assert clamp_period(s, 0.5) == 0.5


# ---------------------------------------------------------------------------
# Decorators (paper Listing 2) + stacking
# ---------------------------------------------------------------------------

def test_measure_decorator_returns_measurements():
    @pmt.measure("dummy")
    def app():
        time.sleep(0.01)
        return "payload"

    measures = app()
    assert isinstance(measures, pmt.Measurements)
    assert measures.result == "payload"
    assert len(measures) == 1
    m = measures[0]
    assert m.sensor == "dummy"
    assert m.seconds >= 0.01
    assert m.joules == pytest.approx(m.watts * m.seconds, rel=1e-6)
    assert "J" in str(m) and "W" in str(m)


def test_stacked_decorators_merge():
    @pmt.measure("tpu")
    @pmt.measure("dummy")
    def app():
        time.sleep(0.005)
        return 7

    measures = app()
    assert {m.sensor for m in measures} == {"tpu", "dummy"}
    assert measures.result == 7
    assert measures.by_sensor("tpu").kind == "modeled"


def test_multi_backend_single_decorator():
    @pmt.measure("dummy", "tpu")
    def app():
        return None

    measures = app()
    assert {m.sensor for m in measures} == {"dummy", "tpu"}
    assert measures.total_joules() >= 0.0


def test_measure_requires_backend():
    with pytest.raises(ValueError):
        pmt.measure()


def test_dump_decorator(tmp_path):
    path = str(tmp_path / "dec.pmt")

    @pmt.dump("dummy", filename=path, period_s=0.005)
    def app():
        time.sleep(0.03)
        return 5

    assert app() == 5  # return value passes through in dump mode
    hdr, recs = pmt.read_dump(path)
    assert len(recs) >= 2


def test_region_context_manager():
    with pmt.Region("dummy", label="roi") as r:
        time.sleep(0.002)
    m = r.measurement
    assert m is not None and m.label == "roi" and m.seconds > 0


def test_decorator_accepts_sensor_instance():
    sensor = pmt.create("dummy", watts=5.0)

    @pmt.measure(sensor)
    def app():
        return 1

    m = app()[0]
    assert m.sensor == "dummy"


# ---------------------------------------------------------------------------
# Metrics (paper §III)
# ---------------------------------------------------------------------------

@given(j=st.floats(1e-9, 1e9), s=st.floats(1e-9, 1e6))
def test_edp_properties(j, s):
    assert pmt.edp(j, s) == pytest.approx(j * s)
    assert pmt.ed2p(j, s) == pytest.approx(j * s * s)
    assert pmt.edp(2 * j, s) > pmt.edp(j, s)


@given(flops=st.floats(1.0, 1e18), j=st.floats(1e-6, 1e9))
def test_gflops_per_watt_identity(flops, j):
    # GFLOP/s/W == flops / joules / 1e9 (seconds cancel)
    g = pmt.gflops_per_watt(flops, j)
    assert g == pytest.approx(flops / j / 1e9)


def test_efficiency_report_csv():
    r = pmt.EfficiencyReport(joules=10.0, seconds=2.0, flops=1e12,
                             tokens=1000)
    assert r.watts == pytest.approx(5.0)
    assert r.gflops_per_watt == pytest.approx(100.0)
    assert r.joules_per_token == pytest.approx(0.01)
    row = r.as_csv_row()
    assert len(row.split(",")) == len(r.CSV_HEADER.split(","))


# ---------------------------------------------------------------------------
# PowerMonitor + straggler detection (framework integration)
# ---------------------------------------------------------------------------

def test_power_monitor_step_attribution(tmp_path):
    log = str(tmp_path / "energy.csv")
    clk = FakeClock()
    sensor = pmt.create("dummy", watts=100.0, clock=clk)
    mon = pmt.PowerMonitor([sensor], log_path=log)
    for i in range(3):
        with mon.measure_step(step=i, flops=1e9, tokens=10) as box:
            clk.advance(1.0)
        assert box.records[0].joules == pytest.approx(100.0)
    assert mon.cumulative_joules == pytest.approx(300.0)
    mon.close()
    lines = open(log).read().strip().splitlines()
    assert lines[0].startswith("step,sensor")
    assert len(lines) == 4


def test_power_monitor_resume_from_checkpoint_energy():
    mon = pmt.PowerMonitor(["dummy"], initial_joules=1234.5)
    assert mon.cumulative_joules == pytest.approx(1234.5)
    sd = mon.state_dict()
    assert sd["cumulative_joules"] == pytest.approx(1234.5)


def test_straggler_detection_requires_both_signals():
    # host 5 is slow AND power-anomalous -> straggler
    v = pmt.detect_stragglers([100, 101, 99, 100, 100, 40],
                              [1.0, 1.01, 0.99, 1.0, 1.0, 3.5])
    assert [x.is_straggler for x in v] == [False] * 5 + [True]
    # slow but power-normal -> data skew, not a straggler
    v2 = pmt.detect_stragglers([100, 101, 99, 100, 100, 100],
                               [1.0, 1.01, 0.99, 1.0, 1.0, 3.5])
    assert not v2[5].is_straggler


def test_straggler_empty_and_mismatch():
    assert pmt.detect_stragglers([], []) == []
    with pytest.raises(ValueError):
        pmt.detect_stragglers([1.0], [1.0, 2.0])


def test_monitor_thread_safety():
    mon = pmt.PowerMonitor(["dummy"])
    errs = []

    def work(i):
        try:
            with mon.measure_step(step=i):
                time.sleep(0.001)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(mon.records()) == 8
