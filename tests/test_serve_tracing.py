"""The serve engine in a profiler trace, and the measurement plane's
own cost counters.

A ``jax.profiler`` trace of a tiny paged ``generate()`` must hold the
engine's phase vocabulary (``engine/*``, the fetches nested in their
parents), a twin annotation for every ``serve/req<N>[/phase]`` record
whose duration agrees with the record's, and the step programs under
their own names.  ``Session.stats()`` and ``engine.stats()`` must count
region entries and exits, sampler ticks, resolver passes and decode
bursts as they happen.
"""
import collections
import dataclasses
import glob
import time

import jax
import numpy as np
import pytest

import repro.core as pmt
from repro import configs
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine

PHASES = ("engine/wait", "engine/admit", "engine/prefill",
          "engine/prefill/fetch", "engine/decode", "engine/decode/fetch",
          "engine/retire")


def _requests(cfg, lens=(13, 21, 30), max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(2, cfg.vocab_size - 1,
                                        size=(p,)).tolist(),
                    max_new_tokens=max_new) for p in lens]


def _host_events(log_dir):
    """``{line: [(name, start_ns, end_ns)]}`` of the trace's host plane."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out[(plane.name, line.name)].append(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced paged ``generate()`` with a session, after a warm-up
    that compiles every step; the requests fall due 50 ms into the call,
    so the engine first waits for them."""
    cfg = dataclasses.replace(configs.get_config("smollm-135m",
                                                 reduced=True),
                              dtype="float32", prefill_chunk=16)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    sess = pmt.Session(["dummy"], pool=pmt.SensorPool())
    mem = sess.add_exporter(pmt.MemoryExporter())
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64, session=sess,
                      kv_layout="paged", kv_page_size=8)
    eng.generate(_requests(cfg, seed=1))
    sess.flush()
    n_warm = len(mem.records)
    stats0 = eng.stats()
    reqs = _requests(cfg, lens=(13, 21, 30, 9))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    for r in reqs:
        r._retry_at = time.monotonic() + 0.05
    eng.generate(reqs)
    jax.profiler.stop_trace()
    stats1 = eng.stats()
    sess.flush()
    records = [r for r in mem.records[n_warm:]
               if r.path.startswith("serve/req")]
    yield dict(cfg=cfg, params=params, eng=eng, sess=sess, reqs=reqs,
               records=records, events=_host_events(log_dir),
               stats0=stats0, stats1=stats1)
    sess.close()


def _engine_line(events):
    return next(evs for evs in events.values()
                if any(n == "engine/admit" for n, _, _ in evs))


def test_phase_vocabulary_with_fetches_nested(traced):
    evs = _engine_line(traced["events"])
    names = {n for n, _, _ in evs}
    assert set(PHASES) <= names
    assert not {n for n in names if n.startswith("engine/")} - set(PHASES)
    for child, parent in (("engine/prefill/fetch", "engine/prefill"),
                          ("engine/decode/fetch", "engine/decode")):
        outer = [(s, e) for n, s, e in evs if n == parent]
        for n, s, e in evs:
            if n == child:
                assert any(ps <= s and e <= pe for ps, pe in outer)


def test_phases_tile_the_loop(traced):
    """Top-level phases do not overlap, and the gaps between them (the
    loop's own control) are a small share of the loop's time."""
    top = sorted((s, e) for n, s, e in _engine_line(traced["events"])
                 if n in PHASES and n.count("/") == 1)
    assert all(a[1] <= b[0] for a, b in zip(top, top[1:]))
    covered = sum(e - s for s, e in top)
    assert covered >= 0.95 * (top[-1][1] - top[0][0])


def test_request_records_have_trace_twins(traced):
    twins = collections.defaultdict(list)
    for evs in traced["events"].values():
        for n, s, e in evs:
            if n.startswith("serve/req"):
                twins[n].append((e - s) * 1e-9)
    recs = traced["records"]
    assert {r.path for r in recs} == set(twins)
    assert len(recs) == 3 * len(traced["reqs"])
    for r in recs:
        (d,) = twins[r.path]
        assert abs(d - (r.end_s - r.start_s)) < 1e-3


def test_twins_without_a_session(traced, tmp_path):
    eng = ServeEngine(traced["cfg"], traced["params"], batch_size=2,
                      max_len=64, kv_layout="paged", kv_page_size=8)
    eng.generate(_requests(traced["cfg"], lens=(9,)))
    jax.profiler.start_trace(str(tmp_path))
    eng.generate(_requests(traced["cfg"], lens=(13,)))
    jax.profiler.stop_trace()
    names = {n for evs in _host_events(str(tmp_path)).values()
             for n, _, _ in evs}
    rid = eng._request_count - 1
    assert {f"serve/req{rid}", f"serve/req{rid}/prefill",
            f"serve/req{rid}/decode"} <= names
    assert "measurement" not in eng.stats()


def test_step_programs_carry_their_names(traced):
    eng = traced["eng"]
    assert eng._paged_decode_step.__name__ == "serve_decode"
    assert eng._paged_prefill_chunk_step.__name__ == "serve_prefill_chunk"
    assert eng._prefill.__name__ == "serve_prefill"
    assert eng._decode.__name__ == "serve_decode"
    names = {n for evs in traced["events"].values() for n, _, _ in evs}
    assert {"PjitFunction(serve_decode)",
            "PjitFunction(serve_prefill_chunk)"} <= names
    assert "PjitFunction(wrapper)" not in names
    # compiled once each, warm-up included; the traced call compiled
    # nothing new
    assert eng.compile_counts["decode"] >= 1
    assert eng.compile_counts["prefill_chunk"] == 1
    assert traced["stats1"]["compile_counts"] \
        == traced["stats0"]["compile_counts"]


def test_engine_counts_decode_occupancy(traced):
    s0, s1 = traced["stats0"], traced["stats1"]
    steps = s1["decode_steps"] - s0["decode_steps"]
    rows = s1["decode_row_steps"] - s0["decode_row_steps"]
    # every request decodes max_new - 1 steps after its prefill token
    assert rows == sum(r.max_new_tokens - 1 for r in traced["reqs"])
    assert 0 < steps <= rows <= steps * s1["batch_slots"]


def test_engine_stats_carry_the_planes_counters(traced):
    m0 = traced["stats0"]["measurement"]
    m1 = traced["stats1"]["measurement"]
    n = len(traced["records"])
    assert m1["region_opens"] - m0["region_opens"] >= n
    assert m1["region_closes"] - m0["region_closes"] >= n
    assert m1["region_s"] > m0["region_s"]
    assert m1["sampler_ticks"] > m0["sampler_ticks"]
    assert m1["sampler_s"] > m0["sampler_s"]
    for k in ("resolved", "pending", "dropped"):
        assert k in m1


def test_session_counters_grow_with_regions_ticks_and_batches():
    with pmt.Session(["dummy"], pool=pmt.SensorPool(),
                     period_s=0.001) as sess:
        s0 = sess.stats()
        for _ in range(5):
            with sess.region("r"):
                pass
        deadline = time.monotonic() + 5.0
        while sess.stats()["resolver_batches"] == s0["resolver_batches"] \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        s1 = sess.stats()
    assert s1["region_opens"] - s0["region_opens"] == 5
    assert s1["region_closes"] - s0["region_closes"] == 5
    assert s1["region_s"] > s0["region_s"]
    assert s1["sampler_ticks"] > s0["sampler_ticks"]
    assert s1["sampler_s"] > s0["sampler_s"]
    assert s1["resolver_batches"] > s0["resolver_batches"]
    assert s1["resolver_s"] > s0["resolver_s"]
    assert {"resolved", "evicted", "degraded", "dropped", "resolve_errors",
            "pending"} <= set(s1)
    assert s0["t_s"] < s1["t_s"]


def test_region_counters_lose_no_update_across_threads():
    """Regions opened and closed on many threads at once, with the
    interpreter switching threads as often as it can: every open and
    close is counted."""
    import sys
    import threading
    n_threads, n_regions = 16, 300
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
            def work():
                for _ in range(n_regions):
                    with sess.region("r", nested=False):
                        pass

            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            st = sess.stats()
    finally:
        sys.setswitchinterval(old)
    assert st["region_opens"] == st["region_closes"] \
        == n_threads * n_regions


def test_ended_threads_fold_their_counters():
    """A thread's region counters outlive it in the session's total,
    and the session keeps no per-thread state for it once it ends."""
    import threading
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        def work():
            for _ in range(3):
                with sess.region("r", nested=False):
                    pass

        for _ in range(20):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
        with sess.region("main"):
            pass
        deadline = time.monotonic() + 5.0
        while len(sess._costs) > 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(sess._costs) == 1       # the main thread's own
        st = sess.stats()
    assert st["region_opens"] == st["region_closes"] == 20 * 3 + 1
    assert st["region_s"] > 0.0
