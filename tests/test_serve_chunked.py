"""Chunked-prefill serve admission: correctness and accounting gates.

Model-level: ``ServeFns.prefill_chunk`` resumed chunk by chunk must
reproduce the whole-prompt (unpadded) prefill — same first token, same
valid cache rows up to one bf16 cache-quantization ulp — across the
cache families (GQA, sliding-window ring, MLA latent, mamba/xlstm scan
carries).

Engine-level: the chunked-interleaved engine must generate the same
tokens as the blocking-bucketed baseline whenever the two compute the
same function (prompts already bucket-sized, so blocking adds no
left-pad context), must be invariant to the chunk size, and must keep
the accounting invariants: phase spans tile request spans, prefill
compiles once at one chunk shape, spans never leak — even when a
prefill chunk raises mid-generate.

Satellites covered here: engine sampling (greedy/temperature/seed) and
``prompt_bucket`` min_bucket validation.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as pmt
from repro import configs
from repro.models import model as M
from repro.serve.engine import (Request, ServeEngine, prompt_bucket,
                                resolve_prefill_chunk)


def rng(i):
    return jax.random.PRNGKey(i)


def _fp32(arch):
    return dataclasses.replace(configs.get_config(arch, reduced=True),
                               dtype="float32")


def mk(reqs):
    return [Request(prompt=list(p), max_new_tokens=n) for p, n in reqs]


def run_chunked_prefill(cfg, params, tokens, chunk, max_len):
    """Drive prefill_chunk over a (1, plen) prompt; returns
    (last logits (1, V), caches)."""
    fns = M.make_serve_fns(cfg)
    caches = M.init_caches(cfg, 1, max_len)
    plen = tokens.shape[1]
    padded = math.ceil(plen / chunk) * chunk
    toks = np.zeros((1, padded), np.int32)
    toks[0, :plen] = np.asarray(tokens)[0]
    pc = jax.jit(fns.prefill_chunk)
    logits = None
    for off in range(0, padded, chunk):
        last_idx = min(plen - 1 - off, chunk - 1)
        logits, caches = pc(params, caches,
                            jnp.asarray(toks[:, off:off + chunk]),
                            jnp.asarray(off, jnp.int32),
                            jnp.asarray(last_idx, jnp.int32))
    return logits, caches


def assert_caches_match(cfg, caches_whole, caches_chunked, plen,
                        atol=2e-2):
    """Compare cache trees on the slots whole-prompt prefill wrote.

    Chunked prefill reads the bf16-quantized prefix where whole-prompt
    prefill attends fp32 pre-cache K/V, so rows agree to one bf16 ulp
    (atol), not bitwise; slots past the prompt hold chunk padding on
    one side and init zeros on the other and are excluded (they are
    invalid under every decode path's cur_len masking)."""
    axes = M.cache_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    ax_leaves = jax.tree.leaves(axes, is_leaf=is_axes)
    wl = jax.tree.leaves(caches_whole)
    cl = jax.tree.leaves(caches_chunked)
    assert len(ax_leaves) == len(wl) == len(cl)
    for ax, a, b in zip(ax_leaves, wl, cl):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert a.shape == b.shape
        if "kv_seq" in ax:
            s_ax = ax.index("kv_seq")
            n = min(plen, a.shape[s_ax])
            sl = [slice(None)] * a.ndim
            sl[s_ax] = slice(0, n)
            a, b = a[tuple(sl)], b[tuple(sl)]
        np.testing.assert_allclose(a, b, atol=atol, rtol=0)


# -- model-level: chunked == whole-prompt prefill ------------------------------

# gemma2 = sliding-window ring + softcap; deepseek = MLA latent cache;
# jamba = mamba scan carry (hybrid); xlstm = mLSTM/sLSTM carries
@pytest.mark.parametrize("arch", ["smollm-135m", "gemma2-27b",
                                  "deepseek-v3-671b", "jamba-v0.1-52b",
                                  "xlstm-1.3b"])
def test_chunked_prefill_matches_whole_prompt(arch):
    cfg = _fp32(arch)
    params, _ = M.init_params(rng(0), cfg)
    plen, chunk, max_len = 13, 4, 32
    tokens = jax.random.randint(rng(1), (1, plen), 0, cfg.vocab_size)
    fns = M.make_serve_fns(cfg)
    logits_w, caches_w = jax.jit(lambda p, b: fns.prefill(p, b, max_len))(
        params, {"tokens": tokens})
    logits_c, caches_c = run_chunked_prefill(cfg, params, tokens, chunk,
                                             max_len)
    # the acceptance gate: same first token, same valid cache rows
    assert int(np.argmax(logits_w)) == int(np.argmax(logits_c))
    np.testing.assert_allclose(np.asarray(logits_w), np.asarray(logits_c),
                               atol=2e-2, rtol=2e-2)
    assert_caches_match(cfg, caches_w, caches_c, plen)


def test_chunked_prefill_invariant_to_chunk_size():
    """The same prompt prefilled at chunk 3, 5, and 16 must land on the
    same first token and near-identical caches — the engine's knob is a
    scheduling choice, not a semantic one."""
    cfg = _fp32("smollm-135m")
    params, _ = M.init_params(rng(0), cfg)
    plen, max_len = 11, 32
    tokens = jax.random.randint(rng(2), (1, plen), 0, cfg.vocab_size)
    results = [run_chunked_prefill(cfg, params, tokens, ck, max_len)
               for ck in (3, 5, 16)]
    toks = {int(np.argmax(np.asarray(l))) for l, _ in results}
    assert len(toks) == 1
    for _, caches in results[1:]:
        assert_caches_match(cfg, results[0][1], caches, plen)


def test_chunked_prefill_ring_prompt_longer_than_window():
    """gemma2 local layers with a prompt well past the ring size: the
    chunked ring writes + trailing-query window masks must agree with
    the whole-prompt path."""
    cfg = _fp32("gemma2-27b")
    params, _ = M.init_params(rng(0), cfg)
    plen = cfg.sliding_window * 2 + 5
    max_len = plen + 11
    tokens = jax.random.randint(rng(3), (1, plen), 0, cfg.vocab_size)
    fns = M.make_serve_fns(cfg)
    logits_w, caches_w = jax.jit(lambda p, b: fns.prefill(p, b, max_len))(
        params, {"tokens": tokens})
    logits_c, caches_c = run_chunked_prefill(cfg, params, tokens, 8,
                                             max_len)
    assert int(np.argmax(logits_w)) == int(np.argmax(logits_c))
    assert_caches_match(cfg, caches_w, caches_c, plen)


def test_prefill_chunk_rejects_encoder_decoder():
    cfg = _fp32("whisper-tiny")
    fns = M.make_serve_fns(cfg)
    with pytest.raises(NotImplementedError, match="encoder-decoder"):
        fns.prefill_chunk(None, None, jnp.zeros((1, 4), jnp.int32), 0, 3)
    with pytest.raises(ValueError, match="encoder-decoder"):
        resolve_prefill_chunk(cfg, 8)
    assert resolve_prefill_chunk(cfg, None) == 0    # silent fallback


# -- engine-level --------------------------------------------------------------

MIXED = [([1, 2, 3], 8), ([4, 5], 3), ([6], 1),
         ([7, 8, 9, 10, 11, 12, 13, 14, 15], 5), ([2], 12),
         ([3, 1, 4, 1, 5], 2), ([9, 9], 7)]


@pytest.fixture(scope="module")
def smollm():
    cfg = _fp32("smollm-135m")
    params, _ = M.init_params(rng(0), cfg)
    return cfg, params


def test_engine_chunked_matches_blocking_on_bucket_sized_prompts(smollm):
    """For prompts already at their bucket size, blocking admission adds
    no left-pad context, so the chunked engine must generate identical
    tokens.  fp32 caches (``cache_dtype``): the reduced test model's
    top-2 logit gaps (~5e-5) sit *below* bf16 cache quantization noise,
    so bf16 would compare cache-rounding luck, not scheduler
    correctness."""
    cfg, params = smollm
    reqs = [(list(range(1, 9)), 6), (list(range(3, 19)), 4),
            ([5] * 8, 3), (list(range(2, 10)), 9)]
    outs = {}
    for chunk in (0, 4, 8):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          prefill_chunk=chunk, cache_dtype=jnp.float32)
        outs[chunk] = [r.out for r in eng.generate(mk(reqs))]
        assert all(len(o) == n for o, (_, n) in zip(outs[chunk], reqs))
    assert outs[4] == outs[0]       # chunked == blocking baseline
    assert outs[8] == outs[4]       # and invariant to the chunk size


def test_engine_chunked_matches_single_request_runs(smollm):
    """Continuous chunked serving at B=3 == each request served alone
    (B=1), byte-identical — the PR3 slot-independence gate holds under
    interleaved chunked admission too."""
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=3, max_len=64,
                      prefill_chunk=4)
    done = eng.generate(mk(MIXED))
    ref_eng = ServeEngine(cfg, params, batch_size=1, max_len=64,
                          prefill_chunk=4)
    for i, (prompt, n) in enumerate(MIXED):
        ref = ref_eng.generate(mk([(prompt, n)]))[0]
        assert done[i].out == ref.out
        assert len(done[i].out) == n


def test_engine_stall_events_recorded(smollm):
    """Chunked admission records one stall sample per fenced chunk run
    while another request is mid-decode, and each is bounded by chunk
    work (vs whole-prompt samples under blocking admission)."""
    cfg, params = smollm
    reqs = mk([(list(range(1, 17)), 6), (list(range(1, 17)), 6),
               (list(range(1, 17)), 6)])
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      prefill_chunk=4)
    eng.generate([dataclasses.replace(r) for r in reqs])
    assert len(eng.stall_events) >= 4    # 16/4 chunks for the refills
    assert all(s >= 0 for s in eng.stall_events)
    eng0 = ServeEngine(cfg, params, batch_size=2, max_len=64,
                       prefill_chunk=0)
    eng0.generate([dataclasses.replace(r) for r in reqs])
    # blocking: one (whole-prompt) stall per admission that finds the
    # batch already decoding
    assert 1 <= len(eng0.stall_events) <= 2


def test_engine_sampling_threads_keys(smollm):
    """greedy=False actually samples: same seed reproduces the exact
    token streams, different seeds diverge, and the distribution is not
    the greedy argmax stream."""
    cfg, params = smollm
    reqs = [(list(range(1, 7)), 12), ([3, 2], 10)]

    def run(**kw):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64, **kw)
        return [r.out for r in eng.generate(mk(reqs))]

    greedy = run()
    s0a = run(greedy=False, temperature=1.5, seed=0)
    s0b = run(greedy=False, temperature=1.5, seed=0)
    s1 = run(greedy=False, temperature=1.5, seed=1)
    assert s0a == s0b               # deterministic under a fixed seed
    assert s0a != s1                # seeds decorrelate
    assert s0a != greedy            # and it is not argmax decoding
    assert all(len(o) == n for o, (_, n) in zip(s0a, reqs))
    with pytest.raises(ValueError, match="temperature"):
        ServeEngine(cfg, params, batch_size=1, max_len=32, greedy=False,
                    temperature=0.0)


def test_engine_prefill_failure_closes_all_spans(smollm):
    """A prefill chunk raising mid-generate must close every open
    serve/req span (request + phases) — Session.stats() ends with no
    pending spans and the flush sees exactly the opened set."""
    cfg, params = smollm
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        mem = sess.add_exporter(pmt.MemoryExporter())
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          prefill_chunk=4, session=sess)
        calls = {"n": 0}
        real = eng._prefill_chunk_fn

        def boom(*args, **kw):
            calls["n"] += 1
            if calls["n"] == 4:     # mid-loop, second admission underway
                raise RuntimeError("injected prefill OOM")
            return real(*args, **kw)

        eng._prefill_chunk_fn = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate(mk([(list(range(1, 9)), 6),
                             (list(range(1, 13)), 6),
                             ([1, 2, 3], 4)]))
        sess.flush()
        st = sess.stats()
        assert st["pending"] == 0
        assert st["resolve_errors"] == 0
        req_paths = {r.path for r in mem.records
                     if r.path.startswith("serve/req")}
        # both admitted requests closed their request span and their
        # open phase spans (the second died mid-prefill: no decode span)
        assert "serve/req0" in req_paths and "serve/req1" in req_paths
        assert "serve/req0/prefill" in req_paths
        assert "serve/req1/prefill" in req_paths
        assert all(np.isfinite(r.joules) for r in mem.records)
    # a fresh generate on the same engine still works (no stuck state)
    eng2 = ServeEngine(cfg, params, batch_size=2, max_len=64,
                       prefill_chunk=4)
    assert [len(r.out) for r in eng2.generate(mk([([1, 2], 3)]))] == [3]


def test_engine_blocking_prefill_failure_closes_all_spans(smollm):
    """Same cleanup gate for the prefill_chunk=0 baseline: a whole-
    prompt prefill raising mid-admission must not leak the admitted
    request's open request/prefill spans."""
    cfg, params = smollm
    with pmt.Session(["dummy"], pool=pmt.SensorPool()) as sess:
        mem = sess.add_exporter(pmt.MemoryExporter())
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          prefill_chunk=0, session=sess)
        calls = {"n": 0}
        real = eng._prefill_request

        def boom(r):
            calls["n"] += 1
            if calls["n"] == 2:     # second admission, first mid-decode
                raise RuntimeError("injected prefill OOM")
            return real(r)

        eng._prefill_request = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.generate(mk([([1, 2, 3], 6), ([4, 5, 6], 4)]))
        sess.flush()
        assert sess.stats()["pending"] == 0
        req_paths = {r.path for r in mem.records
                     if r.path.startswith("serve/req")}
        assert {"serve/req0", "serve/req0/prefill", "serve/req0/decode",
                "serve/req1", "serve/req1/prefill"} <= req_paths


def test_engine_monitor_phase_split(smollm):
    """PowerMonitor path: per_request_energy carries the prefill/decode
    J split and the phases sum to the request total."""
    cfg, params = smollm
    mon = pmt.PowerMonitor(["dummy"])
    try:
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          prefill_chunk=4, monitor=mon)
        reqs = mk(MIXED[:4])
        eng.generate(reqs)
        per = mon.per_request_energy()
        assert sorted(per) == [0, 1, 2, 3]
        for i, d in per.items():
            assert d["tokens"] == MIXED[i][1]
            assert d["prefill_joules"] >= 0.0
            assert d["decode_joules"] >= 0.0
            split = d["prefill_joules"] + d["decode_joules"]
            assert split == pytest.approx(d["joules"], rel=0.05,
                                          abs=1e-3)
        # phase records carry the phase tag; whole-request spans don't
        phases = {r.phase for r in mon.request_records()}
        assert phases == {None, "prefill", "decode"}
    finally:
        mon.close()


# -- satellites ----------------------------------------------------------------

def test_prompt_bucket_min_bucket_must_be_power_of_two():
    assert prompt_bucket(3, min_bucket=2) == 4
    assert prompt_bucket(3, min_bucket=1) == 4
    for bad in (0, 3, 6, 12, -8):
        with pytest.raises(ValueError, match="power of two"):
            prompt_bucket(5, min_bucket=bad)


def test_resolve_prefill_chunk_precedence(smollm, monkeypatch):
    cfg, _ = smollm
    assert resolve_prefill_chunk(cfg, 16) == 16          # arg wins
    assert resolve_prefill_chunk(cfg, 0) == 0
    assert resolve_prefill_chunk(cfg, None) == cfg.prefill_chunk
    monkeypatch.setenv("PMT_PREFILL_CHUNK", "12")
    assert resolve_prefill_chunk(cfg, None) == 12        # env beats cfg
    assert resolve_prefill_chunk(cfg, 16) == 16          # arg beats env
    with pytest.raises(ValueError, match=">= 0"):
        resolve_prefill_chunk(cfg, -1)
