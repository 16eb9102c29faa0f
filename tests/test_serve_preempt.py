"""Preemption-and-resume serve plane: survive pressure without drops.

The load-bearing property is *no request left behind*: under page-pool
exhaustion the engine preempts the lowest-priority live request
page-aligned — its written KV pages checkpoint to the radix tree (cheap,
copy-free) or a host-memory ``PageSwapStore`` — and the request resumes
through the existing chunked-prefill offset machinery once pressure
clears, generating **byte-identical** tokens to an unpressured run
(tree/swap hit = copy-free restore; miss = honest recompute).

Robustness gates riding along: NaN/Inf-logit quarantine (poisoned pages
never seed the prefix tree), bounded retry-with-backoff, engine
watchdog (hung-step detection), graceful ``drain()``/resume, and the
satellite fix that a mid-chunk exception releases reserved pages,
resolves both phase spans, and leaves the engine reusable.

Resume parity shares the prefix-reuse caveat (see test_serve_paged):
the MoE arch resumes on its chunk grid via ``kv_page_size ==
prefill_chunk``; dense archs exercise off-grid resume too.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as pmt
from repro import configs
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine
from repro.serve.swap import PageSwapStore
from repro.telemetry import PowerRecorder

ARCHS = ["smollm-135m", "gemma2-27b", "deepseek-v3-671b"]


def _fp32(arch, **over):
    return dataclasses.replace(configs.get_config(arch, reduced=True),
                               dtype="float32", **over)


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    cfg = _fp32(request.param, prefill_chunk=16)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return request.param, cfg, params


@pytest.fixture(scope="module")
def smollm():
    cfg = _fp32("smollm-135m", prefill_chunk=16)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def mk(cfg, n_req=5, seed=7, max_new=6):
    rng = np.random.default_rng(seed)
    plens = [13, 21, 9, 30, 17]
    return [Request(prompt=rng.integers(
        2, cfg.vocab_size - 1, size=(p,)).tolist(), max_new_tokens=max_new)
        for p, _ in zip(plens * 10, range(n_req))]


def mkpress(cfg):
    """A workload that forces live preemption on an undersized pool:
    a long-decoding request (slot-holding victim), a staggered-prefill
    neighbour, and a page-hungry latecomer whose admission cannot be
    covered while the victim holds its pages."""
    rng = np.random.default_rng(11)
    specs = [(13, 16), (21, 4), (40, 4)]
    return [Request(prompt=rng.integers(2, cfg.vocab_size - 1,
                                        size=(p,)).tolist(),
                    max_new_tokens=n) for p, n in specs]


def _press_pool(ps):
    # Sized so the page-hungry latecomer cannot be covered even after
    # radix eviction while the long decoder holds its reservation:
    # free + evictable-tree < pages_needed forces a live preemption.
    return 9 if ps == 8 else 4


def outs(rs):
    return [r.out for r in rs]


def _ref(cfg, params, rs):
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      cache_dtype=jnp.float32)
    return outs(eng.generate([dataclasses.replace(r) for r in rs]))


# -- tentpole gate: preempted == unpressured, byte-identical -----------------

def test_preempt_under_pressure_matches_unpressured(arch_setup):
    """Pool exhaustion preempts a live request; every request still
    completes with the unpressured engine's exact tokens across the
    cache families (GQA, sliding-window, MLA latent)."""
    arch, cfg, params = arch_setup
    ps = 16 if arch == "deepseek-v3-671b" else 8
    rs = mkpress(cfg)
    ref = _ref(cfg, params, rs)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=ps,
                      kv_pool_pages=_press_pool(ps), preempt=True,
                      cache_dtype=jnp.float32)
    got = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref, f"{arch}: preempted run diverged from unpressured"
    assert eng.preemptions >= 1 and eng.preempt_resumes >= 1
    kc = eng.stats()["kv_cache"]
    assert kc["pages_used"] + kc["pages_free"] == kc["pages_total"]


def test_preempt_swap_roundtrip(smollm):
    """With a swap store the victim's pages leave the device pool
    entirely and come back through the gather/scatter jits: parity
    holds and the store drains to empty once every request retires."""
    cfg, params = smollm
    rs = mkpress(cfg)
    ref = _ref(cfg, params, rs)
    store = PageSwapStore(capacity_bytes=1 << 30)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      swap_store=store, cache_dtype=jnp.float32)
    got = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref
    assert store.swapped_out_pages >= 1
    assert store.swapped_in_pages >= 1
    assert len(store) == 0 and store.bytes_used == 0
    via = [e["via"] for e in eng.preempt_log if e["event"] == "resume"]
    assert "swap" in via


def test_preempt_accounting_reconciles(smollm):
    """stats()["preemption"] totals equal the per-event ``preempt_log``
    sums exactly — the reconciliation the chaos bench gates at <= 1%
    holds with zero error at the counter level."""
    cfg, params = smollm
    rs = mkpress(cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      cache_dtype=jnp.float32)
    eng.generate([dataclasses.replace(r) for r in rs])
    pre = eng.stats()["preemption"]
    log = eng.preempt_log
    resumes = [e for e in log if e["event"] == "resume"]
    preempts = [e for e in log if e["event"] == "preempt"]
    assert pre["preemptions"] == len(preempts) >= 1
    assert pre["resumes"] == len(resumes) >= 1
    assert pre["wasted_tokens"] == sum(e["rerun_tokens"] for e in resumes)
    assert pre["recovered_tokens"] == \
        sum(e["recovered_tokens"] for e in resumes)
    assert pre["wasted_joules"] == pytest.approx(
        sum(e["wasted_joules"] for e in resumes))
    assert pre["recovered_joules"] == pytest.approx(
        sum(e["recovered_joules"] for e in resumes))


def test_priority_orders_victims(smollm):
    """A high-priority latecomer never evicts higher-priority live work
    — the lowest-priority request is the deterministic victim."""
    cfg, params = smollm
    rs = mkpress(cfg)
    rs[0].priority = -1                 # the long decoder: lowest
    rs[1].priority = 5
    rs[2].priority = 5
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      cache_dtype=jnp.float32)
    done = eng.generate([dataclasses.replace(r) for r in rs])
    assert all(r.finish_reason == "length" for r in done)
    victims = {e["rid"] for e in eng.preempt_log
               if e["event"] == "preempt"}
    assert victims == {0}               # only the priority=-1 request


def test_retry_exhaustion_retires(smollm):
    """preempt_max_retries=0: the first preemption retires the victim
    with ``"preempted-retry-exhausted"``, keeping its partial output;
    everything else completes normally."""
    cfg, params = smollm
    rs = mkpress(cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      preempt_max_retries=0, cache_dtype=jnp.float32)
    done = eng.generate([dataclasses.replace(r) for r in rs])
    reasons = [r.finish_reason for r in done]
    assert reasons.count("preempted-retry-exhausted") == 1
    assert all(r == "length" for r in reasons
               if r != "preempted-retry-exhausted")
    exhausted = done[reasons.index("preempted-retry-exhausted")]
    assert 0 < len(exhausted.out) < exhausted.max_new_tokens
    assert eng.retries_exhausted == 1


def test_timeout_outranks_retry_while_preempted(smollm):
    """A preempted request whose deadline lapses while parked in retry
    backoff finishes ``"timeout"`` (not preempted-*), and its swap
    checkpoint is dropped — parked state never outlives its request."""
    cfg, params = smollm
    store = PageSwapStore(capacity_bytes=1 << 30)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      retry_backoff_s=60.0, swap_store=store,
                      cache_dtype=jnp.float32)
    eng.generate(mk(cfg, n_req=2))      # warm the compile cache
    rs = mkpress(cfg)
    rs[0].deadline_s = 2.0              # the victim-to-be
    done = eng.generate([dataclasses.replace(r) for r in rs])
    assert eng.preemptions >= 1
    assert done[0].finish_reason == "timeout"
    assert len(done[0].out) > 0         # partial output kept
    assert all(r.finish_reason == "length" for r in done[1:])
    assert len(store) == 0              # checkpoint dropped on timeout


# -- quarantine --------------------------------------------------------------

def _poison_first_decode(eng):
    """Fabricate non-finite-logit detection for row 0 of the first
    decode dispatch (the KV pages are never actually poisoned, so a
    retried request must reproduce the reference tokens exactly)."""
    orig = eng._paged_decode
    state = {"fired": False}

    def poison(*args):
        t, ok, c = orig(*args)
        if not state["fired"]:
            state["fired"] = True
            ok = ok.at[0].set(False)
        return t, ok, c

    eng._paged_decode = poison


def test_quarantine_retires_with_error(smollm):
    """A non-finite decode step retires the poisoned request with
    ``finish_reason="error"``; its pages are discarded (never adopted)
    and every other request matches the clean reference."""
    cfg, params = smollm
    rs = mk(cfg)
    ref = _ref(cfg, params, rs)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      cache_dtype=jnp.float32)
    _poison_first_decode(eng)
    done = eng.generate([dataclasses.replace(r) for r in rs])
    assert done[0].finish_reason == "error"
    assert len(done[0].out) < done[0].max_new_tokens
    assert eng.quarantined == 1
    assert [r.out for r in done[1:]] == ref[1:]
    kc = eng.stats()["kv_cache"]
    assert kc["pages_used"] + kc["pages_free"] == kc["pages_total"]


def test_quarantine_retry_recovers(smollm):
    """retry_errors=True: the quarantined request restarts from scratch
    (poisoned progress contributes nothing) and, since the fault was
    transient, completes byte-identical to the reference."""
    cfg, params = smollm
    rs = mk(cfg)
    ref = _ref(cfg, params, rs)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      retry_errors=True, cache_dtype=jnp.float32)
    _poison_first_decode(eng)
    got = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref
    assert eng.quarantined == 1


# -- watchdog / drain / telemetry -------------------------------------------

def test_watchdog_flags_hung_steps(smollm):
    """An impossible per-step budget flags every fenced dispatch as
    hung: detection-only (the run still completes) but counted and
    emitted as telemetry events."""
    cfg, params = smollm
    events = []
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      watchdog_step_timeout_s=1e-9,
                      cache_dtype=jnp.float32)
    eng.event_sink = events.append
    done = eng.generate(mk(cfg, n_req=2))
    assert all(r.finish_reason == "length" for r in done)
    assert eng.hung_steps > 0
    assert eng.stats()["preemption"]["hung_steps"] == eng.hung_steps
    kinds = {e["kind"] for e in events}
    assert "hung_step" in kinds
    assert all("timestamp_s" in e for e in events)


def test_drain_checkpoints_and_resumes(smollm):
    """drain() mid-run checkpoints every in-flight request with
    ``finish_reason`` None; re-submitting the same objects resumes them
    to byte-identical completion."""
    cfg, params = smollm
    rs = mkpress(cfg)
    ref = _ref(cfg, params, rs)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      cache_dtype=jnp.float32)
    orig = eng._paged_prefill_chunk_fn
    calls = {"n": 0}

    def drain_after_two(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:
            eng.drain()
        return orig(*a, **k)

    eng.generate(mk(cfg, n_req=1, seed=1))      # warm compile first
    eng._paged_prefill_chunk_fn = drain_after_two
    submitted = [dataclasses.replace(r) for r in rs]
    eng.generate(submitted)
    unfinished = [r for r in submitted if r.finish_reason is None]
    assert unfinished, "drain landed after every request finished"
    assert eng.drains == 1
    eng._paged_prefill_chunk_fn = orig
    eng.generate(unfinished)                    # resume
    assert all(r.finish_reason == "length" for r in submitted)
    assert outs(submitted) == ref


def test_recorder_ingests_preempt_events(smollm):
    """attach_engine wires the engine's event sink: preemption-plane
    events land in the recorder's ring, counted in its stats."""
    cfg, params = smollm
    rs = mkpress(cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=_press_pool(8), preempt=True,
                      cache_dtype=jnp.float32)
    rec = PowerRecorder()
    rec.attach_engine(eng)
    try:
        eng.generate([dataclasses.replace(r) for r in rs])
        evs = rec.preempt_events()
        kinds = {e.kind for e in evs}
        assert {"preempt", "preempt_resume"} <= kinds
        assert rec.stats()["preempt_events"] == len(evs) >= 2
        assert all(isinstance(e.as_json(), str) for e in evs)
    finally:
        rec.close()


# -- satellite: exception cleanup -------------------------------------------

def test_exception_releases_pages_and_closes_spans(smollm):
    """A mid-chunk exception in the paged path must release every
    reserved page, resolve both open phase spans, and leave the engine
    fully reusable — no leaked refs, no dangling measurement state."""
    cfg, params = smollm
    rs = mk(cfg)
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        mem = sess.add_exporter(pmt.MemoryExporter())
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          session=sess, kv_layout="paged", kv_page_size=8,
                          cache_dtype=jnp.float32)
        orig = eng._paged_prefill_chunk_fn

        def boom(*a, **k):
            raise RuntimeError("injected mid-chunk fault")

        eng._paged_prefill_chunk_fn = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate([dataclasses.replace(r) for r in rs])
        assert eng._pool.free_pages == eng._pool.total_pages
        assert eng.live_slots == 0
        sess.flush()
        req_paths = [r.path for r in mem.records
                     if r.path.startswith("serve/req")]
        # both admitted requests resolved their request AND prefill
        # spans despite the mid-chunk raise
        assert len([p for p in req_paths if p.count("/") == 1]) == 2
        assert len([p for p in req_paths if p.endswith("/prefill")]) == 2
        eng._paged_prefill_chunk_fn = orig
        got = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert got == _ref(cfg, params, rs)


# -- validation --------------------------------------------------------------

def test_preempt_validation(smollm):
    cfg, params = smollm
    with pytest.raises(ValueError, match="preempt"):
        ServeEngine(cfg, params, batch_size=2, max_len=64, preempt=True)
    with pytest.raises(ValueError, match="swap_store"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    swap_store=PageSwapStore())
    with pytest.raises(ValueError, match="watchdog"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    watchdog_step_timeout_s=1.0)
    with pytest.raises(ValueError, match="watchdog"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    kv_layout="paged", watchdog_step_timeout_s=0.0)
