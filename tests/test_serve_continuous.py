"""Continuous-batching serve engine: correctness and accounting gates.

The load-bearing property is *slot independence*: per-slot decoding with
mixed prompt/generation lengths must produce byte-identical outputs to
serving each request alone (same engine, batch 1), including left-padded
edge rows and slots refilled mid-run — any KV leak between sequences or
positional mixup breaks exact token equality immediately.

Accounting gates: aggregate regions count *actually generated* tokens
(never ``batch * max_steps``), per-request spans resolve with token
counts summing to the aggregate, and the decode step function never
recompiles across request mixes (prompt lengths are bucketed).
"""
import dataclasses

import jax
import numpy as np
import pytest

import repro.core as pmt
from repro import configs
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine, prompt_bucket


@pytest.fixture(scope="module")
def smollm():
    cfg = dataclasses.replace(configs.get_config("smollm-135m",
                                                 reduced=True),
                              dtype="float32")
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


MIXED = [([1, 2, 3], 8), ([4, 5], 3), ([6], 1),
         ([7, 8, 9, 10, 11, 12, 13, 14, 15], 5), ([2], 12),
         ([3, 1, 4, 1, 5], 2), ([9, 9], 7)]


def mk(reqs):
    return [Request(prompt=list(p), max_new_tokens=n) for p, n in reqs]


def test_continuous_byte_identical_to_single_request(smollm):
    """B=3 continuous decode == each request served alone (B=1), exactly.

    The mix covers: prompts shorter than the min bucket (heavy left
    padding), max_new=1 (retired at prefill), more requests than slots
    (every slot refills at least once), and interleaved retirements.
    """
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=3, max_len=64)
    done = eng.generate(mk(MIXED))
    ref_eng = ServeEngine(cfg, params, batch_size=1, max_len=64)
    for i, (prompt, n) in enumerate(MIXED):
        ref = ref_eng.generate(mk([(prompt, n)]))[0]
        assert done[i].out == ref.out, (
            f"request {i} diverged from single-request reference: "
            f"{done[i].out} != {ref.out}")
        assert len(done[i].out) == n


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_moe_continuous_byte_identical_to_single_request(layout):
    """The same gate for a MoE model (DeepSeek-V3's routing at its
    reduced size): serving routes drop-free, so no capacity couples a
    row to its slot neighbours."""
    cfg = dataclasses.replace(
        configs.get_config("deepseek-v3-671b", reduced=True),
        dtype="float32", prefill_chunk=8)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(max_len=64, kv_layout=layout)
    if layout == "paged":
        kw.update(kv_page_size=8)
    done = ServeEngine(cfg, params, batch_size=3, **kw).generate(mk(MIXED))
    ref_eng = ServeEngine(cfg, params, batch_size=1, **kw)
    for i, (prompt, n) in enumerate(MIXED):
        ref = ref_eng.generate(mk([(prompt, n)]))[0]
        assert done[i].out == ref.out, (i, done[i].out, ref.out)
        assert len(done[i].out) == n


def test_slot_refill_leaks_no_kv(smollm):
    """A request decoded in a freshly-refilled slot matches its own
    solo run regardless of which request occupied the slot before —
    run the same mix in two different queue orders."""
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64)
    a = {tuple(r.prompt): r.out for r in eng.generate(mk(MIXED))}
    b = {tuple(r.prompt): r.out
         for r in eng.generate(mk(list(reversed(MIXED))))}
    assert a == b


def test_decode_never_recompiles_across_mixes(smollm):
    """Chunked admission (the default): decode AND prefill each compile
    exactly once, no matter what prompt lengths arrive — the bucket
    family is gone."""
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64)
    eng.generate(mk(MIXED[:3]))
    assert eng.compile_counts == {"prefill": 0, "decode": 1,
                                  "prefill_chunk": 1}
    # different prompt lengths (crossing what used to be bucket
    # boundaries), different generation lengths, different request count
    eng.generate(mk([([5, 4, 3, 2], 6), ([1], 9), ([8, 8, 8, 8, 8, 8], 2),
                     ([2, 3], 4), (list(range(1, 17)), 2)]))
    assert eng.compile_counts == {"prefill": 0, "decode": 1,
                                  "prefill_chunk": 1}


def test_blocking_baseline_compiles_once_per_bucket(smollm):
    """The prefill_chunk=0 baseline keeps the old bucketed-jit-cache
    property: one blocking prefill compile per power-of-two bucket."""
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      prefill_chunk=0)
    eng.generate(mk(MIXED[:3]))
    decode_compiles = eng.compile_counts["decode"]
    prefill_compiles = eng.compile_counts["prefill"]
    eng.generate(mk([([5, 4, 3, 2], 6), ([1], 9), ([8, 8, 8, 8, 8, 8], 2),
                     ([2, 3], 4)]))
    assert eng.compile_counts["decode"] == decode_compiles == 1
    assert eng.compile_counts["prefill"] == prefill_compiles
    assert eng.compile_counts["prefill_chunk"] == 0
    # a new bucket compiles blocking prefill exactly once more
    eng.generate(mk([(list(range(1, 17)), 2)]))
    assert eng.compile_counts["prefill"] == prefill_compiles + 1
    assert eng.compile_counts["decode"] == 1


def test_prompt_bucketing():
    assert prompt_bucket(1) == 8
    assert prompt_bucket(8) == 8
    assert prompt_bucket(9) == 16
    assert prompt_bucket(100) == 128
    assert prompt_bucket(3, min_bucket=2) == 4
    with pytest.raises(ValueError):
        prompt_bucket(0)


def test_request_validation(smollm):
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16)
    # chunked admission fits what bucketing couldn't: 9 prompt tokens
    # pad to 16 (one whole-cache chunk), and 9 + 2 decode slots <= 17
    assert [len(r.out) for r in eng.generate(mk([([1] * 9, 2)]))] == [2]
    with pytest.raises(ValueError, match="cache slots"):
        eng.generate(mk([([1] * 15, 3)]))     # 15 + 3 > 17 decode slots
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.generate(mk([([1], 0)]))
    # the blocking baseline keeps the bucket-based capacity check
    eng0 = ServeEngine(cfg, params, batch_size=1, max_len=16,
                       prefill_chunk=0)
    with pytest.raises(ValueError, match="cache slots"):
        eng0.generate(mk([([1] * 9, 2)]))     # bucket 16 + 2 > 17


def test_wave_region_counts_generated_tokens(smollm):
    """Satellite fix: wave J/token divides by sum(max_new_tokens), not
    batch * max_steps (which counted idle-slot padding as work)."""
    cfg, params = smollm
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        mem = sess.add_exporter(pmt.MemoryExporter())
        eng = ServeEngine(cfg, params, batch_size=2, max_len=32,
                          session=sess, mode="wave")
        eng.generate(mk([([1, 2], 2), ([3], 6)]))   # one wave, 6 steps
        sess.flush()
        waves = [r for r in mem.records if r.path.startswith("serve/wave")]
        assert waves and all(r.tokens == 8 for r in waves)  # not 2*6=12


def test_per_request_spans_sum_to_aggregate(smollm):
    cfg, params = smollm
    reqs = mk(MIXED[:5])
    total = sum(r.max_new_tokens for r in reqs)
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        mem = sess.add_exporter(pmt.MemoryExporter())
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          session=sess)
        eng.generate(reqs)
        sess.flush()
        agg = [r for r in mem.records if r.path.startswith("serve/batch")]
        per_req = [r for r in mem.records
                   if r.path.startswith("serve/req")
                   and "/" not in r.path.replace("serve/", "")]
        phases = [r for r in mem.records
                  if r.path.startswith("serve/req")
                  and "/" in r.path.replace("serve/", "")]
        assert [r.tokens for r in agg] == [total]
        assert len(per_req) == len(reqs)
        assert sum(r.tokens for r in per_req) == total
        # every request gets exactly one prefill + one decode phase
        # span, tiling its request span (dummy backend: constant watts,
        # so the J split must sum to the request total up to the tiny
        # uncovered instants between back-to-back clock reads)
        for r in per_req:
            mine = [p for p in phases if p.path.startswith(r.path + "/")]
            assert sorted(p.path.rsplit("/", 1)[1] for p in mine) == \
                ["decode", "prefill"]
            split = sum(p.joules for p in mine)
            assert split == pytest.approx(r.joules, rel=0.05, abs=1e-3)
        # flat spans: no nesting path pollution, every span resolves
        assert all(r.depth == 0 for r in per_req + phases)
        assert all(r.seconds >= 0 and np.isfinite(r.joules)
                   for r in per_req + phases)
        assert sess.stats()["pending"] == 0


def test_monitor_per_request_accounting(smollm):
    cfg, params = smollm
    mon = pmt.PowerMonitor(["dummy"])
    try:
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          monitor=mon)
        reqs = mk(MIXED[:4])
        eng.generate(reqs)
        per = mon.per_request_energy()
        assert sorted(per) == [0, 1, 2, 3]
        assert [per[i]["tokens"] for i in range(4)] == \
            [n for _, n in MIXED[:4]]
        for d in per.values():
            assert d["j_per_token"] >= 0.0
        # step records (the aggregate batch region) stay separate
        assert all(r.scope == "request" for r in mon.request_records())
        steps = [r for r in mon.records() if r.scope == "step"]
        assert steps and steps[0].tokens == sum(n for _, n in MIXED[:4])
    finally:
        mon.close()


def test_vector_positions_match_scalar(smollm):
    """decode_step with a (B,) position vector of equal entries must be
    bit-identical to the scalar path it generalises."""
    import jax.numpy as jnp
    cfg, params = smollm
    B, T = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    prefill, decode, _ = M.make_serve_fns(cfg)
    _, caches = jax.jit(lambda p, b: prefill(p, b, T + 4))(
        params, {"tokens": tokens[:, :T - 1]})
    nxt = tokens[:, T - 1:T]
    l_s, c_s = jax.jit(decode)(params, caches, nxt,
                               jnp.asarray(T - 1, jnp.int32))
    l_v, c_v = jax.jit(decode)(params, caches, nxt,
                               jnp.full((B,), T - 1, jnp.int32))
    assert bool(jnp.array_equal(l_s, l_v))
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)))
