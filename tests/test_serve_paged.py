"""Paged-KV serve engine: byte-parity, prefix reuse, and pool gates.

The load-bearing property is *layout transparency*: the paged engine —
page pools, per-request page tables, radix prefix cache, batched chunk
admissions — must generate exactly the tokens the contiguous engine
generates for the same requests.  Block-paging changes where KV bytes
live, never what the model computes.

Prefix-reuse gate: re-serving prompts whose pages sit in the radix tree
must produce the same tokens as the cold run.  This is exact when the
resume offset lands on the cold run's chunk grid, which the tests force
with ``kv_page_size == prefill_chunk`` for the MoE arch (off-grid
resumes reorder float reductions by ~1 ulp, which can flip near-tied
router top-k choices in random-init reduced models — dense archs are
gated off-grid precisely because they don't amplify).

Pool gates: admissions wait (not fail) on an exhausted pool, the
engine refuses pools smaller than one request, and the stats() cache
gauges stay consistent with the pool/radix state they mirror.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine

ARCHS = ["smollm-135m", "gemma2-27b", "deepseek-v3-671b"]


def _fp32(arch, **over):
    # MoE layers serve drop-free: no capacity couples a row to the batch
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


def outs(rs):
    return [r.out for r in rs]


# -- tentpole gate: paged == contiguous, exactly ----------------------------

def test_paged_matches_contiguous(arch_setup):
    """Same requests, same scheduler: the paged engine's token streams
    equal the contiguous engine's across the cache families (GQA,
    sliding-window, MLA latent) — including slot refills (5 requests
    through 2 slots) and batched multi-row chunk admissions."""
    arch, cfg, params = arch_setup
    rs = mk(cfg)
    eng_c = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        cache_dtype=jnp.float32)
    ref = outs(eng_c.generate([dataclasses.replace(r) for r in rs]))
    eng_p = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        kv_layout="paged", kv_page_size=8,
                        cache_dtype=jnp.float32)
    got = outs(eng_p.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref, f"{arch}: paged diverged from contiguous"


def test_paged_pool_exhaustion_waits(smollm):
    """A pool that fits ~one request forces admissions to wait on page
    frees; every request still completes with contiguous-exact tokens."""
    cfg, params = smollm
    rs = mk(cfg)
    eng_c = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        cache_dtype=jnp.float32)
    ref = outs(eng_c.generate([dataclasses.replace(r) for r in rs]))
    eng_s = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        kv_layout="paged", kv_page_size=8,
                        kv_pool_pages=9, cache_dtype=jnp.float32)
    got = outs(eng_s.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref
    st = eng_s.stats()["kv_cache"]
    assert st["pages_total"] == 9


# -- prefix-reuse gate ------------------------------------------------------

def test_prefix_hit_matches_cold(arch_setup):
    """Serving the same prompts twice: the warm run maps cached pages
    copy-free off the radix tree (hit tokens accrue) and generates the
    cold run's exact tokens.  ``kv_page_size == prefill_chunk`` keeps
    the MoE arch's resume on the cold chunk grid; the dense archs use a
    smaller page so off-grid resume is exercised too."""
    arch, cfg, params = arch_setup
    ps = 16 if arch == "deepseek-v3-671b" else 8
    rs = mk(cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=ps,
                      cache_dtype=jnp.float32)
    cold = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    st0 = eng.stats()["kv_cache"]
    assert st0["prefix_hit_tokens"] == 0
    assert st0["pages_used"] > 0      # retired pages live on in the tree
    warm = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    st1 = eng.stats()["kv_cache"]
    assert warm == cold, f"{arch}: prefix-hit run diverged from cold"
    assert st1["prefix_hit_tokens"] > 0
    assert st1["prefix_hits"] > 0
    assert 0.0 < st1["prefix_hit_rate"] <= 1.0


def test_prefix_cache_off_is_isolated(smollm):
    """``prefix_cache=False``: no pages survive retirement, reruns take
    no hits, tokens still match the cached engine's cold run."""
    cfg, params = smollm
    rs = mk(cfg)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      prefix_cache=False, cache_dtype=jnp.float32)
    a = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    b = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert a == b
    st = eng.stats()["kv_cache"]
    assert st["prefix_cache"] is False
    assert st["prefix_hit_tokens"] == 0
    assert st["pages_used"] == 0      # everything back on the free list


def test_radix_eviction_under_pool_pressure(smollm):
    """A pool too small to hold every retired prompt forces
    ``evict_for`` to reclaim LRU tree pages at admission; serving
    distinct prompts through it stays correct and evictions surface in
    the gauges."""
    cfg, params = smollm
    rng = np.random.default_rng(3)
    rs = [Request(prompt=rng.integers(2, cfg.vocab_size - 1,
                                      size=(24,)).tolist(),
                  max_new_tokens=4) for _ in range(6)]
    eng_c = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        cache_dtype=jnp.float32)
    ref = outs(eng_c.generate([dataclasses.replace(r) for r in rs]))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8, kv_pool_pages=8,
                      cache_dtype=jnp.float32)
    got = outs(eng.generate([dataclasses.replace(r) for r in rs]))
    assert got == ref
    assert eng.stats()["kv_cache"]["prefix_evictions"] > 0


# -- gauges and lifecycle ---------------------------------------------------

def test_cache_gauges_consistent(smollm):
    """stats()["kv_cache"] mirrors the pool: used + free == total, and
    a fresh engine starts fully free."""
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      cache_dtype=jnp.float32)
    st = eng.stats()
    assert st["kv_layout"] == "paged"
    kc = st["kv_cache"]
    assert kc["page_size"] == 8
    assert kc["pages_used"] == 0
    assert kc["pages_free"] == kc["pages_total"] == 2 * (64 // 8)
    eng.generate(mk(cfg, n_req=3))
    kc = eng.stats()["kv_cache"]
    assert kc["pages_used"] + kc["pages_free"] == kc["pages_total"]
    assert kc["saved_prefill_joules"] >= 0.0


def test_contiguous_engine_reports_no_pool(smollm):
    # contiguous engines report the dtype/footprint gauges but none of
    # the pool/radix keys that only exist in the paged layout
    cfg, params = smollm
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      cache_dtype=jnp.float32)
    st = eng.stats()
    assert st["kv_layout"] == "contiguous"
    kc = st["kv_cache"]
    assert kc["cache_dtype"] == "float32"
    assert kc["bytes_per_token"] > 0
    for key in ("pages_total", "prefix_hits", "pool_wait_events"):
        assert key not in kc


def test_paged_validation(smollm):
    cfg, params = smollm
    with pytest.raises(ValueError, match="kv_layout"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    kv_layout="mystery")
    # pool must fit at least one full request's pages
    with pytest.raises(ValueError, match="pool"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    kv_layout="paged", kv_page_size=8, kv_pool_pages=7)
    # paged requires chunked continuous admission
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    kv_layout="paged", mode="wave")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, batch_size=2, max_len=64,
                    kv_layout="paged", prefill_chunk=0)


def test_paged_admission_batching(smollm):
    """Queued admissions prefill *together*: with both slots admitting
    simultaneously, one batched chunk dispatch advances both rows, so
    the prefill dispatch count stays well under one-per-request-chunk."""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    rs = [Request(prompt=rng.integers(2, cfg.vocab_size - 1,
                                      size=(32,)).tolist(),
                  max_new_tokens=2) for _ in range(4)]
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                      kv_layout="paged", kv_page_size=8,
                      cache_dtype=jnp.float32)
    calls = {"n": 0}
    orig = eng._paged_prefill_chunk_fn

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    eng._paged_prefill_chunk_fn = counting
    eng.generate(rs)
    solo_chunks = sum(-(-len(r.prompt) // cfg.prefill_chunk) for r in rs)
    assert calls["n"] < solo_chunks, (
        f"{calls['n']} batched dispatches vs {solo_chunks} per-request "
        "chunks — admissions are not sharing dispatches")
    assert all(len(r.out) == 2 for r in rs)
