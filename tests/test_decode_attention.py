"""Flash-decode kernel family: parity gates for the serve hot path.

Three layers of gates, tightest first:

  * kernel-level: the Pallas kernel (interpret mode) must match the
    blockwise ``ref.py`` oracle *bit-exactly* — the kernel only adds
    block skipping, which is a bit-neutral update (see ref.py), so any
    fp difference is a real bug, not tolerance noise.  The bucketed
    lax fallback computes each prefix in one fused pass instead of
    blockwise, so it matches within ~1 ulp of fp32 softmax
    reassociation, and must be invariant to scalar-vs-vector
    ``cur_len`` bit-exactly.
  * model-level: ``decode_attn_impl="flash"`` decode logits must match
    the dense path within fp-reassociation tolerance across the cache
    families (GQA, sliding-window ring, MLA latent), for scalar and
    per-row vector ``cur_len``.
  * engine-level: a continuous-batching ``ServeEngine`` with the knob
    flipped must produce byte-identical generated tokens.

Plus the satellite guard: ``attention(impl="pallas")`` refuses args the
flash kernel silently dropped before (kv_valid, cross-attention).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro import configs
from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_lax,
                                            decode_attention_pallas,
                                            decode_attention_ref)
from repro.models import model as M


def rng(i):
    return jax.random.PRNGKey(i)


def make_qkv(key, b, kvh, g, hdq, hdv, c, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, kvh, g, hdq)).astype(dtype)
    k = jax.random.normal(k2, (b, c, kvh, hdq)).astype(dtype)
    v = jax.random.normal(k3, (b, c, kvh, hdv)).astype(dtype)
    return q, k, v


# -- kernel-level: bit-exact vs the blockwise oracle ---------------------------

@pytest.mark.parametrize("kvh,g", [(4, 1), (2, 4), (1, 8)])  # G = 1, 4, H
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_decode_kernel_bit_exact_vs_ref(kvh, g, ring, softcap):
    """One (B,) lens vector covers every fill class at once: empty-ish,
    mid, last-slot, and (ring wrap / clamped) beyond-capacity rows."""
    b, hdq, hdv, c, bk = 5, 32, 24, 64, 16
    q, k, v = make_qkv(rng(1), b, kvh, g, hdq, hdv, c)
    lens = jnp.array([0, 1, c // 2, c - 1, c + c // 2], jnp.int32)
    kw = dict(ring=ring, softcap=softcap, scale=1.0 / math.sqrt(hdq),
              block_k=bk)
    ref = decode_attention_ref(q, k, v, lens, **kw)
    pal = decode_attention_pallas(q, k, v, lens, interpret=True, **kw)
    lax = decode_attention_lax(q, k, v, lens, **kw)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))
    assert_allclose(np.asarray(lax), np.asarray(ref), rtol=2e-6,
                    atol=2e-6)
    assert np.isfinite(np.asarray(ref)).all()


def test_decode_kernel_single_block_and_odd_sizes():
    # single-block cache (block_k >= C) and a cache size that forces
    # the gcd fallback block (40 with block_k=16 -> bk=8)
    for c, bk in [(32, 128), (40, 16)]:
        q, k, v = make_qkv(rng(2), 2, 2, 3, 16, 16, c)
        lens = jnp.array([c // 3, c - 1], jnp.int32)
        kw = dict(ring=True, softcap=None, scale=0.25, block_k=bk)
        ref = decode_attention_ref(q, k, v, lens, **kw)
        pal = decode_attention_pallas(q, k, v, lens, interpret=True, **kw)
        # Interpreter reassociation, not a kernel difference: at a
        # 32-wide block the CPU backend accumulates the kernel's 2-D
        # p @ v dot and the oracle's batched einsum in different orders
        # (~1 ulp of fp32); the blocking and masking are still the
        # oracle's, which the bitwise sweeps above pin.
        assert_allclose(np.asarray(pal), np.asarray(ref), rtol=2e-6,
                        atol=1e-6)


def test_decode_kernel_bf16():
    q, k, v = make_qkv(rng(3), 2, 2, 4, 32, 32, 64, dtype=jnp.bfloat16)
    lens = jnp.array([5, 63], jnp.int32)
    kw = dict(ring=False, softcap=None, scale=1.0 / math.sqrt(32))
    ref = decode_attention_ref(q, k, v, lens, **kw)
    pal = decode_attention_pallas(q, k, v, lens, interpret=True, **kw)
    assert pal.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(pal, np.float32),
                                  np.asarray(ref, np.float32))


def test_decode_ops_scalar_equals_vector():
    """The ops wrapper broadcasts a scalar cur_len to the (B,) vector
    path — results must be bit-identical (the continuous-batching
    invariant the engine relies on)."""
    b, h, kvh, hd, c = 3, 8, 2, 32, 64
    q = jax.random.normal(rng(4), (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(rng(5), (b, c, kvh, hd), jnp.float32)
    v = jax.random.normal(rng(6), (b, c, kvh, hd), jnp.float32)
    for impl in ("lax", "pallas_interpret"):
        o_s = decode_attention(q, k, v, 17, impl=impl, scale=0.2)
        o_v = decode_attention(q, k, v, jnp.full((b,), 17, jnp.int32),
                               impl=impl, scale=0.2)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_v))
        assert o_s.shape == (b, 1, h, hd)


def test_decode_ops_v_width_alias():
    """MLA passes the concatenated [latent | rope] cache as both K and
    V with v_width: must equal attending with an explicitly sliced V,
    on both dispatch paths, under jit."""
    b, h, c, r, rope = 2, 4, 64, 32, 16
    q = jax.random.normal(rng(7), (b, 1, h, r + rope), jnp.float32)
    kv = jax.random.normal(rng(8), (b, c, 1, r + rope), jnp.float32)
    lens = jnp.array([9, c - 1], jnp.int32)
    explicit = jax.jit(lambda q, kv, l: decode_attention(
        q, kv, kv[..., :r], l, impl="lax", scale=0.1))(q, kv, lens)
    for impl in ("lax", "pallas_interpret"):
        alias = jax.jit(
            lambda q, kv, l, i=impl: decode_attention(
                q, kv, kv, l, impl=i, scale=0.1, v_width=r))(q, kv, lens)
        assert alias.shape == (b, 1, h, r)
        if impl == "lax":      # same impl -> identical ops -> bitwise
            np.testing.assert_array_equal(np.asarray(alias),
                                          np.asarray(explicit))
        else:                  # blockwise kernel vs fused pass: ~1 ulp
            assert_allclose(np.asarray(alias), np.asarray(explicit),
                            rtol=2e-6, atol=2e-6)


def test_decode_ops_validation():
    q = jnp.zeros((2, 2, 4, 8))       # Sq != 1
    k = jnp.zeros((2, 16, 2, 8))
    with pytest.raises(ValueError, match="one query token"):
        decode_attention(q, k, k, 0, impl="lax")
    with pytest.raises(ValueError, match="divisible"):
        decode_attention(jnp.zeros((2, 1, 3, 8)), k, k, 0, impl="lax")
    with pytest.raises(ValueError, match="unknown decode_attention"):
        decode_attention(jnp.zeros((2, 1, 4, 8)), k, k, 0, impl="nope")


def test_decode_compiled_path_rejects_untileable_blocks():
    """C=36 with block_k=16 degrades to 4-row blocks: the compiled path
    names the sizes instead of emitting a block the TPU compiler would
    refuse; interpret mode (the CPU reference) still takes them."""
    q, k, v = make_qkv(rng(9), 2, 2, 2, 16, 16, 36)
    lens = jnp.array([5, 35], jnp.int32)
    with pytest.raises(ValueError, match="4 rows along an axis of 36"):
        decode_attention_pallas(q, k, v, lens, block_k=16)
    ref = decode_attention_ref(q, k, v, lens, block_k=16)
    pal = decode_attention_pallas(q, k, v, lens, block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))


# -- model-level: flash vs dense across cache families -------------------------

def _fp32(arch):
    return dataclasses.replace(configs.get_config(arch, reduced=True),
                               dtype="float32")


# gemma2 = sliding-window ring + softcap; deepseek = MLA latent cache
@pytest.mark.parametrize("arch", ["smollm-135m", "gemma2-27b",
                                  "deepseek-v3-671b"])
def test_decode_impl_flash_matches_dense(arch):
    cfg = _fp32(arch)
    params, _ = M.init_params(rng(0), cfg)
    b, t = 2, 12
    tokens = jax.random.randint(rng(1), (b, t), 0, cfg.vocab_size)
    prefill = M.make_serve_fns(cfg).prefill
    _, caches = jax.jit(lambda p, bt: prefill(p, bt, t + 4))(
        params, {"tokens": tokens[:, :t - 1]})
    nxt = tokens[:, t - 1:t]
    logits = {}
    for impl in ("dense", "flash"):
        cfg_i = dataclasses.replace(cfg, decode_attn_impl=impl)
        decode = M.make_serve_fns(cfg_i).decode
        l_s, _ = jax.jit(decode)(params, caches, nxt,
                                 jnp.asarray(t - 1, jnp.int32))
        l_v, _ = jax.jit(decode)(params, caches, nxt,
                                 jnp.full((b,), t - 1, jnp.int32))
        # scalar and per-row vector positions stay bit-identical
        assert bool(jnp.array_equal(l_s, l_v)), impl
        logits[impl] = np.asarray(l_s)
    assert_allclose(logits["flash"], logits["dense"], rtol=2e-4, atol=2e-4)


def test_decode_impl_flash_ring_long_decode():
    """Flash decode far past the sliding window: the ring wraps, every
    step stays finite and tracks the dense path."""
    cfg = dataclasses.replace(_fp32("gemma2-27b"), decode_attn_impl="flash")
    cfg_d = dataclasses.replace(cfg, decode_attn_impl="dense")
    params, _ = M.init_params(rng(0), cfg)
    n = cfg.sliding_window * 2
    tokens = jax.random.randint(rng(2), (1, n), 0, cfg.vocab_size)
    prefill = M.make_serve_fns(cfg).prefill
    _, caches = jax.jit(lambda p, bt: prefill(p, bt, n + 8))(
        params, {"tokens": tokens[:, :8]})
    caches_d = jax.tree.map(lambda x: x, caches)
    dec_f = jax.jit(M.make_serve_fns(cfg)[1])
    dec_d = jax.jit(M.make_serve_fns(cfg_d)[1])
    for t in range(8, 8 + cfg.sliding_window + 6):
        cur = jnp.asarray(t, jnp.int32)
        lf, caches = dec_f(params, caches, tokens[:, t:t + 1], cur)
        ld, caches_d = dec_d(params, caches_d, tokens[:, t:t + 1], cur)
        assert np.isfinite(np.asarray(lf)).all()
        assert_allclose(np.asarray(lf), np.asarray(ld), rtol=2e-4,
                        atol=2e-4)


# -- engine-level: byte parity with the knob flipped ---------------------------

def test_serve_engine_byte_parity_across_decode_impls():
    from repro.serve.engine import Request, ServeEngine
    cfg = _fp32("smollm-135m")
    params, _ = M.init_params(rng(0), cfg)
    mixed = [([1, 2, 3], 8), ([4, 5], 3), ([6], 1), ([2], 12),
             ([7, 8, 9, 10, 11], 5)]
    outs = {}
    for impl in ("dense", "flash"):
        eng = ServeEngine(cfg, params, batch_size=2, max_len=64,
                          decode_attn_impl=impl)
        assert eng.cfg.decode_attn_impl == impl
        done = eng.generate([Request(prompt=list(p), max_new_tokens=nt)
                             for p, nt in mixed])
        outs[impl] = [r.out for r in done]
        assert all(len(o) == nt for o, (_, nt) in zip(outs[impl], mixed))
    assert outs["flash"] == outs["dense"]


def test_decode_attn_impl_resolution(monkeypatch):
    from repro.models import blocks
    cfg = _fp32("smollm-135m")
    assert blocks.decode_attn_impl(
        dataclasses.replace(cfg, decode_attn_impl="flash")) == "flash"
    on_tpu = jax.default_backend() == "tpu"
    assert blocks.decode_attn_impl(cfg) == ("flash" if on_tpu else "dense")
    monkeypatch.setenv("PMT_DECODE_ATTN_IMPL", "flash")
    assert blocks.decode_attn_impl(cfg) == "flash"     # env flips "auto"
    # an explicit config value beats the env var
    assert blocks.decode_attn_impl(
        dataclasses.replace(cfg, decode_attn_impl="dense")) == "dense"
    with pytest.raises(ValueError, match="decode_attn_impl"):
        blocks.decode_attn_impl(
            dataclasses.replace(cfg, decode_attn_impl="nope"))


# -- satellite: attention(impl="pallas") refuses args it would drop ------------

def test_attention_pallas_rejects_unsupported_args():
    from repro.models import attention as A
    cfg = _fp32("smollm-135m")
    b, s, h, kvh, hd = 1, 16, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.zeros((b, s, h, hd), jnp.float32)
    k = jnp.zeros((b, s, kvh, hd), jnp.float32)
    v = jnp.zeros((b, s, kvh, hd), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="kv_valid"):
        A.attention(cfg, q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                    kv_valid=jnp.ones((b, s), bool), impl="pallas")
    with pytest.raises(ValueError, match="causal"):
        A.attention(cfg, q, k, v, q_pos=pos, kv_pos=pos, causal=False,
                    impl="pallas")
    from repro.sharding.specs import split_params
    cross_p, _ = split_params(A.init_attention(rng(0), cfg, cross=True))
    with pytest.raises(ValueError, match="causal"):
        A.cross_attention(cfg, cross_p,
                          jnp.zeros((b, s, cfg.d_model), jnp.float32),
                          jnp.zeros((b, s, cfg.d_model), jnp.float32),
                          impl="pallas")
