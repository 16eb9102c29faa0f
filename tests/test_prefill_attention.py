"""Chunked-prefill kernel family: parity gates for serve admission.

Same three-tier structure as the flash-decode gates
(tests/test_decode_attention.py), tightest first:

  * kernel-level: the Pallas kernel (interpret mode) must match the
    blockwise ``ref.py`` oracle *bit-exactly* — the kernel only adds
    cache-block skipping, which is a bit-neutral update (see ref.py),
    so any fp difference is a real bug, not tolerance noise.  The
    fused-lax fallback computes one dense masked softmax over
    [prefix ++ chunk], so it matches within fp32 reassociation.
  * layer-level: ``prefill_chunk_self_attention`` resumed chunk by
    chunk must reproduce a single whole-sequence ``attention`` call —
    written cache rows bitwise (same projections of the same inputs),
    outputs to fp tolerance — including ring caches whose chunk
    queries trail the newest prefix position (the window mask decode
    never needs).
  * ops-level: dispatch validation, scalar == vector offsets bitwise,
    v_width aliasing (the MLA latent cache).

Model- and engine-level chunked-vs-whole gates live in
tests/test_serve_chunked.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels.prefill_attention import (prefill_attention,
                                             prefill_attention_lax,
                                             prefill_attention_pallas,
                                             prefill_attention_ref)


def rng(i):
    return jax.random.PRNGKey(i)


def make_inputs(key, b, kvh, g, hdq, hdv, c, t, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, kvh, t, g, hdq)).astype(dtype)
    kx = jax.random.normal(ks[1], (b, t, kvh, hdq)).astype(dtype)
    vx = jax.random.normal(ks[2], (b, t, kvh, hdv)).astype(dtype)
    kc = jax.random.normal(ks[3], (b, c, kvh, hdq)).astype(dtype)
    vc = jax.random.normal(ks[4], (b, c, kvh, hdv)).astype(dtype)
    return q, kx, vx, kc, vc


# -- kernel-level: bit-exact vs the blockwise oracle ---------------------------

@pytest.mark.parametrize("kvh,g", [(4, 1), (2, 4), (1, 8)])  # G = 1, 4, H
@pytest.mark.parametrize("ring,window", [(False, None), (True, 24),
                                         (True, 7)])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_prefill_kernel_bit_exact_vs_ref(kvh, g, ring, window, softcap):
    """One (B,) offsets vector covers every resume class at once: cold
    start (offset 0 — no cache block valid), tiny prefix, mid, full,
    and (ring) wrapped-past-capacity."""
    b, hdq, hdv, c, t, bk = 5, 32, 24, 64, 16, 16
    q, kx, vx, kc, vc = make_inputs(rng(1), b, kvh, g, hdq, hdv, c, t)
    offs = jnp.array([0, 1, c // 2, c - t,
                      c + c // 2 if ring else c - 1], jnp.int32)
    kw = dict(ring=ring, window=window, softcap=softcap,
              scale=1.0 / math.sqrt(hdq), block_k=bk)
    ref = prefill_attention_ref(q, kx, vx, kc, vc, offs, **kw)
    pal = prefill_attention_pallas(q, kx, vx, kc, vc, offs,
                                   interpret=True, **kw)
    lax = prefill_attention_lax(q, kx, vx, kc, vc, offs, **kw)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))
    assert_allclose(np.asarray(lax), np.asarray(ref), rtol=2e-6, atol=2e-6)
    assert np.isfinite(np.asarray(ref)).all()


def test_prefill_kernel_single_block_and_odd_sizes():
    # single-block cache/chunk (block_k >= size) and sizes that force
    # the gcd fallback blocks (c=40, t=6 with block_k=16 -> 8 / 2)
    for c, t, bk in [(32, 8, 128), (40, 6, 16)]:
        q, kx, vx, kc, vc = make_inputs(rng(2), 2, 2, 3, 16, 16, c, t)
        offs = jnp.array([c // 3, c - t], jnp.int32)
        kw = dict(ring=True, window=c // 2, softcap=None, scale=0.25,
                  block_k=bk)
        ref = prefill_attention_ref(q, kx, vx, kc, vc, offs, **kw)
        pal = prefill_attention_pallas(q, kx, vx, kc, vc, offs,
                                       interpret=True, **kw)
        # Interpreter reassociation at the 32-wide cache block (see
        # test_decode_kernel_single_block_and_odd_sizes): ~1 ulp of fp32.
        assert_allclose(np.asarray(pal), np.asarray(ref), rtol=2e-6,
                        atol=1e-6)


def test_prefill_kernel_bf16():
    q, kx, vx, kc, vc = make_inputs(rng(3), 2, 2, 4, 32, 32, 64, 8,
                                    dtype=jnp.bfloat16)
    offs = jnp.array([5, 63], jnp.int32)
    kw = dict(ring=False, window=None, softcap=None,
              scale=1.0 / math.sqrt(32))
    ref = prefill_attention_ref(q, kx, vx, kc, vc, offs, **kw)
    pal = prefill_attention_pallas(q, kx, vx, kc, vc, offs,
                                   interpret=True, **kw)
    assert pal.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(pal, np.float32),
                                  np.asarray(ref, np.float32))


def test_prefill_kernel_mixed_cache_dtype():
    """The serve path reads a bf16 cache with fp32 chunk activations —
    both impls must consume each operand in its own dtype."""
    q, kx, vx, kc, vc = make_inputs(rng(4), 2, 2, 2, 16, 16, 32, 8)
    kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    offs = jnp.array([3, 17], jnp.int32)
    kw = dict(scale=0.25)
    ref = prefill_attention_ref(q, kx, vx, kc, vc, offs, **kw)
    pal = prefill_attention_pallas(q, kx, vx, kc, vc, offs,
                                   interpret=True, **kw)
    # Interpreter reassociation at the 32-wide cache block (see
    # test_decode_kernel_single_block_and_odd_sizes): ~1 ulp of fp32.
    assert_allclose(np.asarray(pal), np.asarray(ref), rtol=2e-6, atol=1e-6)


# -- ops-level -----------------------------------------------------------------

def test_prefill_ops_scalar_equals_vector():
    b, t, h, kvh, hd, c = 3, 8, 8, 2, 32, 64
    q = jax.random.normal(rng(5), (b, t, h, hd), jnp.float32)
    kx = jax.random.normal(rng(6), (b, t, kvh, hd), jnp.float32)
    kc = jax.random.normal(rng(7), (b, c, kvh, hd), jnp.float32)
    for impl in ("lax", "pallas_interpret"):
        o_s = prefill_attention(q, kx, kx, kc, kc, 17, impl=impl,
                                scale=0.2)
        o_v = prefill_attention(q, kx, kx, kc, kc,
                                jnp.full((b,), 17, jnp.int32),
                                impl=impl, scale=0.2)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_v))
        assert o_s.shape == (b, t, h, hd)


def test_prefill_ops_v_width_alias():
    """MLA passes the concatenated [latent | rope] rows as both K and V
    with v_width — must equal attending explicitly sliced values, on
    both dispatch paths, under jit."""
    b, t, h, c, r, rope = 2, 8, 4, 40, 32, 16
    q = jax.random.normal(rng(8), (b, t, h, r + rope), jnp.float32)
    kvx = jax.random.normal(rng(9), (b, t, 1, r + rope), jnp.float32)
    kvc = jax.random.normal(rng(10), (b, c, 1, r + rope), jnp.float32)
    offs = jnp.array([0, c - t], jnp.int32)
    explicit = prefill_attention(q, kvx, kvx[..., :r], kvc, kvc[..., :r],
                                 offs, impl="lax", scale=0.1)
    for impl in ("lax", "pallas_interpret"):
        alias = jax.jit(
            lambda q, kvx, kvc, o, i=impl: prefill_attention(
                q, kvx, kvx, kvc, kvc, o, impl=i, scale=0.1,
                v_width=r))(q, kvx, kvc, offs)
        assert alias.shape == (b, t, h, r)
        tol = dict(rtol=0, atol=0) if impl == "lax" else \
            dict(rtol=2e-6, atol=2e-6)
        assert_allclose(np.asarray(alias), np.asarray(explicit), **tol)


def test_prefill_ops_validation():
    q = jnp.zeros((2, 8, 4, 16))
    kx = jnp.zeros((2, 8, 2, 16))
    kc = jnp.zeros((2, 32, 2, 16))
    with pytest.raises(ValueError, match="chunk keys"):
        prefill_attention(q, kc, kc, kc, kc, 0, impl="lax")
    with pytest.raises(ValueError, match="divisible"):
        prefill_attention(jnp.zeros((2, 8, 3, 16)), kx, kx, kc, kc, 0,
                          impl="lax")
    with pytest.raises(ValueError, match="window"):
        prefill_attention(q, kx, kx, kc, kc, 0, ring=True, impl="lax")
    with pytest.raises(ValueError, match="window"):
        prefill_attention(q, kx, kx, kc, kc, 0, window=8, impl="lax")
    with pytest.raises(ValueError, match="unknown prefill_attention"):
        prefill_attention(q, kx, kx, kc, kc, 0, impl="nope")
    # a 20-token chunk splits into 4-row blocks at block_k=16: fine for the
    # interpreter, refused by name on the compiled path
    q20 = jnp.zeros((2, 20, 4, 16))
    kx20 = jnp.zeros((2, 20, 2, 16))
    with pytest.raises(ValueError, match="chunk blocks: block of 4 rows"):
        prefill_attention(q20, kx20, kx20, kc, kc, 0, block_k=16,
                          impl="pallas")


def test_prefill_dispatch_env_override(monkeypatch):
    from repro.kernels.prefill_attention import ops
    monkeypatch.setenv("PMT_PREFILL_ATTENTION_DISPATCH", "pallas_interpret")
    assert ops._resolve("auto") == "pallas_interpret"
    assert ops._resolve("lax") == "lax"          # explicit beats env
    monkeypatch.delenv("PMT_PREFILL_ATTENTION_DISPATCH")
    assert ops._resolve("auto") in ("pallas", "lax")


# -- layer-level: chunked resume == whole-sequence attention -------------------

@pytest.mark.parametrize("window", [None, 16, 5])
def test_layer_chunked_prefill_matches_whole(window):
    """Drive ``prefill_chunk_self_attention`` chunk by chunk over a
    prompt (fp32 cache so quantization cannot hide drift) and compare
    against one whole-sequence ``attention`` call: written cache rows
    must match bitwise, outputs to fp tolerance.  Covers full caches
    and ring caches shorter than the prompt."""
    import dataclasses

    from repro import configs
    from repro.models import attention as A
    from repro.sharding.specs import split_params

    cfg = dataclasses.replace(configs.get_config("smollm-135m",
                                                 reduced=True),
                              dtype="float32", sliding_window=window)
    p, _ = split_params(A.init_attention(rng(0), cfg))
    b, s, chunk, max_len = 2, 24, 8, 32
    x = jax.random.normal(rng(1), (b, s, cfg.d_model), jnp.float32) * 0.3

    # whole-sequence reference (dense attention + prefill cache build)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    q, k, v = A.project_qkv(cfg, p, x, pos)
    o_ref = A.attention(cfg, q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        window=window, impl="dense")
    out_ref = A.output_proj(p, o_ref)
    cache_ref = A.prefill_kv_cache(cfg, k, v, max_len, window=window,
                                   dtype=jnp.float32)

    # chunked resume
    size = min(max_len, window) if window else max_len
    cache = {"k": jnp.zeros((b, size, cfg.num_kv_heads, cfg.head_dim),
                            jnp.float32)}
    cache["v"] = cache["k"]
    outs = []
    for off in range(0, s, chunk):
        o, cache = A.prefill_chunk_self_attention(
            cfg, p, x[:, off:off + chunk], cache,
            jnp.asarray(off, jnp.int32), jnp.asarray(chunk, jnp.int32),
            window=window)
        outs.append(o)
    out = jnp.concatenate(outs, axis=1)

    assert_allclose(np.asarray(out), np.asarray(out_ref), rtol=2e-5,
                    atol=2e-5)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[leaf]),
                                      np.asarray(cache_ref[leaf]))


def test_layer_partial_final_chunk_pads_masked():
    """A right-padded final chunk must leave ring caches exactly as a
    pad-free run does: pad writes would wrap onto valid older
    positions."""
    import dataclasses

    from repro import configs
    from repro.models import attention as A
    from repro.sharding.specs import split_params

    window = 8
    cfg = dataclasses.replace(configs.get_config("smollm-135m",
                                                 reduced=True),
                              dtype="float32", sliding_window=window)
    p, _ = split_params(A.init_attention(rng(0), cfg))
    b, plen, chunk = 1, 13, 8
    x = jax.random.normal(rng(2), (b, plen, cfg.d_model), jnp.float32) * 0.3

    def run(x_padded, valid_lens):
        cache = {"k": jnp.zeros((b, window, cfg.num_kv_heads,
                                 cfg.head_dim), jnp.float32)}
        cache["v"] = cache["k"]
        for i, off in enumerate(range(0, x_padded.shape[1], chunk)):
            _, cache = A.prefill_chunk_self_attention(
                cfg, p, x_padded[:, off:off + chunk], cache,
                jnp.asarray(off, jnp.int32),
                jnp.asarray(valid_lens[i], jnp.int32), window=window)
        return cache

    # padded: 13 -> 16, final chunk has 5 valid tokens + 3 pads whose
    # ring slots (13..15) % 8 = 5..7 hold positions 5..7 — in-window!
    x_pad = jnp.concatenate(
        [x, jnp.full((b, 16 - plen, cfg.d_model), 7.7, jnp.float32)],
        axis=1)
    cache_pad = run(x_pad, [chunk, plen - chunk])
    # reference: exact-length chunks, no pads (chunk == remaining)
    cache_exact = run(x, [chunk, plen - chunk])
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_pad[leaf]),
                                      np.asarray(cache_exact[leaf]))
