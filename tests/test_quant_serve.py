"""Quantized KV caches through the serve stack: accuracy + accounting.

Gates, coarsest last:

  * model-level logit drift: serve-path decode logits with a quantized
    cache must stay within a small *relative* bound of the full-
    precision cache across the cache families (GQA smollm, sliding-
    window gemma2 ring, MLA deepseek — whose latent rows quantize once
    and serve as both key and value).  Bounds are relative to the logit
    magnitude: MoE archs amplify absolute drift through top-k routing
    flips, but the relative excursion stays small (measured: int8
    <= ~1%, fp8 <= ~3% of max |logit| on these reduced configs).
  * engine-level: ``ServeEngine(cache_dtype="int8"/"fp8_e4m3")`` serves
    requests end-to-end in both KV layouts; paged and contiguous agree
    token-for-token under the same mode.
  * stats gauges: ``stats()["kv_cache"]`` reports the cache dtype and
    bytes/token for BOTH layouts, quantized ~half the bf16 footprint.
  * energy accounting under mixed precision: ``saved_prefill_joules``
    must price prefix-cache hits at the *engine's own* learned J/token
    EWMA — a quantized engine learns from its quantized prefill spans,
    never a bf16 engine's price.
  * pool_wait (scheduler fairness): an exhausted pool with an empty
    radix tree logs a ``pool_wait`` governor decision (and bumps the
    engine gauge) instead of silently spinning at admission
    checkpoints.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine
from repro.serve.governor import PowerGovernor

MODES = ("int8", "fp8_e4m3")
B, T = 2, 32

# relative logit-drift gates (fraction of max |logit|), per mode —
# doubled headroom over the measured drift on these reduced configs
DRIFT_GATE = {"int8": 0.10, "fp8_e4m3": 0.20}


def fp32(arch):
    return dataclasses.replace(configs.get_config(arch, reduced=True),
                               dtype="float32")


def serve_logits(cfg, params, tokens, nxt=None):
    """Prefill + two decode steps (write-then-read of quantized rows).
    The second step feeds ``nxt`` (default: the first step's greedy
    token), so that two caches are compared on the same tokens."""
    prefill, decode, _ = M.make_serve_fns(cfg, cache_dtype=jnp.float32)
    _, caches = jax.jit(lambda p, b: prefill(p, b, T + 4))(
        params, {"tokens": tokens[:, :T - 1]})
    lg, caches = jax.jit(decode)(params, caches, tokens[:, T - 1:T],
                                 jnp.asarray(T - 1, jnp.int32))
    if nxt is None:
        nxt = jnp.argmax(lg, -1)[:, None].astype(tokens.dtype)
    lg2, _ = jax.jit(decode)(params, caches, nxt, jnp.asarray(T, jnp.int32))
    return np.asarray(lg), np.asarray(lg2), nxt


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("arch", ["smollm-135m", "gemma2-27b",
                                  "deepseek-v3-671b"])
def test_quant_logit_drift_gate(arch, mode):
    cfg = fp32(arch)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    ref1, ref2, nxt = serve_logits(cfg, params, tokens)
    q1, q2, _ = serve_logits(dataclasses.replace(cfg, kv_quant=mode), params,
                             tokens, nxt)
    assert np.isfinite(q1).all() and np.isfinite(q2).all()
    bound = DRIFT_GATE[mode] * max(float(np.max(np.abs(ref1))), 1.0)
    assert float(np.max(np.abs(q1 - ref1))) < bound
    assert float(np.max(np.abs(q2 - ref2))) < bound


@pytest.mark.parametrize("mode", MODES)
def test_quant_chunked_prefill_consistency(mode):
    # chunked prefill writes the cache chunk-by-chunk (later chunks
    # attend quantized earlier rows); decode logits must stay close to
    # the whole-prompt prefill's
    cfg = dataclasses.replace(fp32("smollm-135m"), kv_quant=mode)
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                cfg.vocab_size)
    prefill, decode, prefill_chunk = M.make_serve_fns(
        cfg, cache_dtype=jnp.float32)
    _, full = jax.jit(lambda p, b: prefill(p, b, T + 4))(
        params, {"tokens": tokens})
    caches = M.init_caches(cfg, B, T + 4, dtype=jnp.float32)
    h = T // 2
    for i in range(2):
        _, caches = jax.jit(prefill_chunk)(
            params, caches, tokens[:, i * h:(i + 1) * h],
            jnp.asarray(i * h, jnp.int32), jnp.asarray(h - 1, jnp.int32))
    nxt = tokens[:, :1]
    l_full, _ = jax.jit(decode)(params, full, nxt, jnp.asarray(T, jnp.int32))
    l_chunk, _ = jax.jit(decode)(params, caches, nxt,
                                 jnp.asarray(T, jnp.int32))
    d = float(np.max(np.abs(np.asarray(l_full) - np.asarray(l_chunk))))
    assert d < 0.08 * max(float(np.max(np.abs(np.asarray(l_full)))), 1.0)


# -- engine level -------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    cfg = fp32("smollm-135m")
    params, _ = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8], [1, 1, 2, 3, 5]]


def run_engine(cfg, params, cache_dtype, kv_layout, max_new=6, **kw):
    eng = ServeEngine(cfg, params, batch_size=2, max_len=48,
                      prefill_chunk=8, kv_layout=kv_layout,
                      cache_dtype=cache_dtype, **kw)
    done = eng.generate([Request(prompt=p, max_new_tokens=max_new)
                         for p in PROMPTS])
    return [r.out for r in done], eng


@pytest.mark.parametrize("mode", MODES)
def test_engine_quant_serves_both_layouts(smollm, mode):
    cfg, params = smollm
    contig, _ = run_engine(cfg, params, mode, "contiguous")
    paged, _ = run_engine(cfg, params, mode, "paged", kv_page_size=8)
    assert all(len(o) == 6 for o in contig)
    # same mode, same rows -> same tokens in either layout
    assert contig == paged


def test_engine_cache_dtype_string_aliases(smollm):
    cfg, params = smollm
    o_arr, _ = run_engine(cfg, params, jnp.bfloat16, "contiguous")
    o_str, _ = run_engine(cfg, params, "bfloat16", "contiguous")
    assert o_arr == o_str
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, batch_size=2, max_len=48,
                    cache_dtype="int4")


def test_engine_kv_cache_gauges(smollm):
    cfg, params = smollm
    _, e_bf = run_engine(cfg, params, jnp.bfloat16, "contiguous")
    _, e_i8 = run_engine(cfg, params, "int8", "contiguous")
    _, p_i8 = run_engine(cfg, params, "int8", "paged", kv_page_size=8)
    kc_bf = e_bf.stats()["kv_cache"]
    kc_i8 = e_i8.stats()["kv_cache"]
    kp_i8 = p_i8.stats()["kv_cache"]
    assert kc_bf["cache_dtype"] == "bfloat16"
    assert kc_i8["cache_dtype"] == kp_i8["cache_dtype"] == "int8"
    # int8 codes + amortized f32 scales land well under the bf16 cache
    assert kc_i8["bytes_per_token"] < 0.6 * kc_bf["bytes_per_token"]
    assert kp_i8["bytes_per_token"] > 0
    # paged gauge carries the pool keys too
    assert kp_i8["pages_total"] > 0 and "pool_wait_events" in kp_i8


@dataclasses.dataclass
class _Rec:
    path: str
    tokens: int
    joules: float


def test_saved_joules_priced_at_own_ewma(smollm):
    # Mixed-precision fleet: a quantized engine's prefix-cache savings
    # must be priced at the J/token EWMA learned from ITS OWN prefill
    # spans, not a bf16 engine's.  Feed each engine a different
    # measured prefill price, replay the same prefix-heavy workload,
    # and check the savings split accordingly.
    cfg, params = smollm
    prices = {"bfloat16": 2.0, "int8": 0.5}
    saved = {}
    for cache_dtype, jpt in prices.items():
        eng = ServeEngine(cfg, params, batch_size=2, max_len=48,
                          prefill_chunk=8, kv_layout="paged",
                          kv_page_size=4, cache_dtype=cache_dtype)
        eng.on_record(_Rec(path="serve/req0/prefill", tokens=4,
                           joules=4 * jpt))
        assert eng._prefill_jpt == pytest.approx(jpt)
        prompt = list(range(1, 13))
        eng.generate([Request(prompt=prompt, max_new_tokens=4)])
        eng.generate([Request(prompt=prompt, max_new_tokens=4)])
        st = eng.stats()["kv_cache"]
        assert st["prefix_hit_tokens"] > 0
        assert st["saved_prefill_joules"] == pytest.approx(
            st["prefix_hit_tokens"] * jpt)
        saved[cache_dtype] = st["saved_prefill_joules"]
    assert saved["int8"] < saved["bfloat16"]


def test_pool_wait_logged_not_silent(smollm):
    # Pool exhausted + radix empty: admission defers, and the wait is
    # SURFACED — a pool_wait governor decision opens the episode and a
    # pool_ready closes it when retirement frees pages (satellite:
    # previously the scheduler spun silently through this checkpoint).
    cfg, params = smollm
    gov = PowerGovernor(recorder=None)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=48,
                      prefill_chunk=8, kv_layout="paged", kv_page_size=8,
                      kv_pool_pages=7, prefix_cache=False,
                      cache_dtype="int8", governor=gov)
    done = eng.generate([Request(prompt=p, max_new_tokens=20)
                         for p in PROMPTS])
    assert all(len(r.out) == 20 for r in done)
    assert eng.pool_wait_events >= 1
    assert eng.stats()["kv_cache"]["pool_wait_events"] >= 1
    actions = [d.action for d in gov.decisions]
    assert "pool_wait" in actions and "pool_ready" in actions
    wait = next(d for d in gov.decisions if d.action == "pool_wait")
    assert "pages" in wait.detail
    # episodes pair up: every wait eventually resolved
    assert actions.count("pool_wait") == actions.count("pool_ready")
