"""Quantized-KV kernel family: parity gates for int8 / fp8_e4m3 caches.

Same three-tier structure as the bf16 kernel gates, applied to the
quantized paths:

  * quantize/dequantize round-trip properties — bounded relative error,
    fp8 casts never produce NaN (the format has no inf, so out-of-range
    casts NaN unless clipped first — ``kernels/quant`` clips), zero rows
    survive the SCALE_EPS floor;
  * cache-update: the fused quantize+scatter Pallas kernels (interpret
    mode) must match the quantize-then-oracle-scatter refs bit-exactly,
    contiguous and paged;
  * attention: decode/prefill kernels dequantizing codes in-register
    inside the online-softmax loop must match their blockwise ``ref.py``
    twins bit-exactly (interpret mode) and the fused lax fallbacks to
    fp32-reassociation tolerance — across full, ring, paged, windowed,
    and MLA ``v_width``-alias (scales quantized ONCE, serving as both
    key and value scale) cache families.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quant
from repro.kernels.cache_update.cache_update import (
    quant_cache_update_pallas, quant_paged_cache_update_pallas)
from repro.kernels.cache_update.ops import (quant_cache_update,
                                            quant_paged_cache_update)
from repro.kernels.cache_update.ref import (quant_cache_update_ref,
                                            quant_paged_cache_update_ref)
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_paged_pallas, decode_attention_pallas)
from repro.kernels.decode_attention.ops import (decode_attention_lax,
                                                decode_attention_paged_lax)
from repro.kernels.decode_attention.ref import (decode_attention_paged_ref,
                                                decode_attention_ref)
from repro.kernels.prefill_attention.ops import (prefill_attention_lax,
                                                 prefill_attention_paged_lax)
from repro.kernels.prefill_attention.prefill_attention import (
    prefill_attention_paged_pallas, prefill_attention_pallas)
from repro.kernels.prefill_attention.ref import (prefill_attention_paged_ref,
                                                 prefill_attention_ref)

MODES = list(quant.QUANT_MODES)
B, C, T, KVH, G, HD = 3, 64, 8, 2, 4, 16
PS, NB, P = 8, 8, 32
RK = 24          # MLA latent+rope width (v_width=8 slice alias)
# Paged pools are layer stacks (L, P, PS, ...) read and written at one
# layer: a stack of one, and an inner layer of three.
STACKS = [(1, 0), (3, 1)]


def rng(i):
    return np.random.default_rng(i)


def bitexact(a, b):
    assert np.array_equal(np.asarray(a), np.asarray(b))


def close(a, b, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol)


# -- quantize/dequantize properties -------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_quant_roundtrip_bounded(mode):
    x = jnp.asarray(rng(0).normal(size=(B, C, KVH, HD)) * 5, jnp.float32)
    codes, scales = quant.quantize(x, mode)
    assert codes.dtype == quant.quant_dtype(mode)
    assert scales.dtype == jnp.float32 and scales.shape == x.shape[:-1]
    y = np.asarray(quant.dequantize(codes, scales))
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    # absmax scheme: error bounded by half a step of the row's range
    step = amax / (127.0 if mode == "int8" else 448.0)
    tol = 0.51 * step if mode == "int8" else 0.07 * amax + 1e-6
    assert np.all(np.abs(y - np.asarray(x)) <= tol + 1e-7)


@pytest.mark.parametrize("mode", MODES)
def test_quant_no_nan_extremes(mode):
    # fp8_e4m3 casts NaN out-of-range values (no inf encoding); the
    # quantizer must clip first.  Also: all-zero rows hit the SCALE_EPS
    # floor instead of dividing by zero.
    x = np.zeros((2, 4, HD), np.float32)
    x[0, 0] = 1e30
    x[0, 1] = -1e30
    x[1, 2] = 1e-30
    codes, scales = quant.quantize(jnp.asarray(x), mode)
    y = np.asarray(quant.dequantize(codes, scales))
    assert np.isfinite(y).all()
    assert np.all(y[1, :2] == 0.0) and np.all(y[0, 2:] == 0.0)


def test_quant_mode_validation():
    with pytest.raises(ValueError):
        quant.quantize(jnp.zeros((2, 4)), "int4")
    with pytest.raises(ValueError):
        quant.quant_dtype("bf16")


# -- cache_update: fused quantize+scatter vs quantize-then-oracle -------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("heads", [(KVH, HD), (1, RK)])
def test_quant_cache_update_parity(mode, heads):
    h, d = heads
    r = rng(1)
    shape = (B, C, h, d) if h > 1 else (B, C, d)
    cache = quant.quantize(
        jnp.asarray(r.normal(size=shape), jnp.float32), mode)[0]
    scales = jnp.zeros(shape[:-1], jnp.float32)
    new = jnp.asarray(r.normal(size=(B, 1) + shape[2:]) * 3, jnp.float32)
    slots = jnp.asarray([0, 17, 63], jnp.int32)
    ref_c, ref_s = quant_cache_update_ref(cache, scales, new, slots, mode)
    out_c, out_s = quant_cache_update(cache, scales, new, slots, mode,
                                      impl="pallas_interpret")
    bitexact(ref_c, out_c)
    bitexact(ref_s, out_s)
    # written rows round-trip the incoming values (fp8_e4m3 carries a
    # 3-bit mantissa: ~6% relative error on top of the absmax step)
    deq = np.asarray(quant.dequantize(ref_c, ref_s))
    for b, s in enumerate([0, 17, 63]):
        row = np.asarray(new)[b, 0]
        amax = float(np.max(np.abs(row)))
        tol = 0.51 * amax / 127 if mode == "int8" else 0.07 * amax
        close(deq[b, s], row, atol=tol)


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("mode", MODES)
def test_quant_paged_cache_update_parity(mode, n_layers, layer):
    r = rng(2)
    pool = quant.quantize(jnp.asarray(
        r.normal(size=(n_layers, P, PS, KVH, HD)), jnp.float32), mode)[0]
    scales = jnp.asarray(r.uniform(size=(n_layers, P, PS, KVH)), jnp.float32)
    new = jnp.asarray(r.normal(size=(B, T, KVH, HD)) * 2, jnp.float32)
    pt = jnp.asarray(r.permutation(P - 1)[: B * NB].reshape(B, NB) + 1,
                     jnp.int32)
    starts = jnp.asarray([0, 5, 30], jnp.int32)
    valids = jnp.asarray([T, 4, T], jnp.int32)
    ref_p, ref_s = quant_paged_cache_update_ref(
        pool[layer:layer + 1], scales[layer:layer + 1], 0, new, pt, starts,
        valids, mode)
    out_p, out_s = quant_paged_cache_update(pool, scales, layer, new, pt,
                                            starts, valids, mode,
                                            impl="pallas_interpret")
    bitexact(ref_p[0], out_p[layer])
    bitexact(ref_s[0], out_s[layer])
    others = [i for i in range(n_layers) if i != layer]
    bitexact(np.asarray(out_p)[others], np.asarray(pool)[others])
    bitexact(np.asarray(out_s)[others], np.asarray(scales)[others])


# -- decode attention: in-register dequant vs ref / lax -----------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("ring", [False, True])
def test_quant_decode_parity(mode, ring):
    r = rng(3)
    q = jnp.asarray(r.normal(size=(B, KVH, G, HD)), jnp.float32)
    k = jnp.asarray(r.normal(size=(B, C, KVH, HD)) * 3, jnp.float32)
    v = jnp.asarray(r.normal(size=(B, C, KVH, HD)), jnp.float32)
    lens = jnp.asarray([5, 63, 130 if ring else 31], jnp.int32)
    kc, ks = quant.quantize(k, mode)
    vc, vs = quant.quantize(v, mode)
    ref = decode_attention_ref(q, kc, vc, lens, ring=ring, scale=0.3,
                               block_k=16, k_scale=ks, v_scale=vs)
    pl = decode_attention_pallas(q, kc, vc, lens, ring=ring, scale=0.3,
                                 block_k=16, k_scale=ks, v_scale=vs,
                                 interpret=True)
    lx = decode_attention_lax(q, kc, vc, lens, ring=ring, scale=0.3,
                              k_scale=ks, v_scale=vs)
    bitexact(ref, pl)
    close(ref, lx)


@pytest.mark.parametrize("mode", MODES)
def test_quant_decode_mla_alias(mode):
    # MLA latent rows quantize ONCE; the same codes+scales serve as key
    # (full width) and value (v_width prefix).  Slice-then-dequant ==
    # dequant-then-slice, so v_scale defaults to k_scale.
    r = rng(4)
    kv = jnp.asarray(r.normal(size=(B, C, 1, RK)), jnp.float32)
    q1 = jnp.asarray(r.normal(size=(B, 1, G, RK)), jnp.float32)
    lens = jnp.asarray([5, 20, 63], jnp.int32)
    kvc, kvs = quant.quantize(kv, mode)
    ref = decode_attention_ref(q1, kvc, kvc[..., :8], lens, scale=0.3,
                               block_k=16, k_scale=kvs, v_scale=kvs)
    pl = decode_attention_pallas(q1, kvc, kvc, lens, scale=0.3, block_k=16,
                                 v_width=8, k_scale=kvs, interpret=True)
    lx = decode_attention_lax(q1, kvc, kvc, lens, scale=0.3, v_width=8,
                              k_scale=kvs)
    bitexact(ref, pl)
    close(ref, lx)


def quantized_stack(r, n_layers, shape, mode, spread=1.0):
    """Codes and scales of a (n_layers, *shape) pool of normal draws."""
    x = jnp.asarray(r.normal(size=(n_layers, *shape)) * spread, jnp.float32)
    return quant.quantize(x, mode)


def at(layer, *pools):
    """Each pool's ``[layer]`` as a stack of one (the one-pool contract)."""
    return [p[layer:layer + 1] for p in pools]


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("window", [None, 24])
def test_quant_decode_paged_parity(mode, window, n_layers, layer):
    r = rng(5)
    kpc, kps = quantized_stack(r, n_layers, (P, PS, KVH, HD), mode, 2)
    vpc, vps = quantized_stack(r, n_layers, (P, PS, KVH, HD), mode)
    pt = jnp.asarray(r.permutation(P - 1)[: B * NB].reshape(B, NB) + 1,
                     jnp.int32)
    lens = jnp.asarray([3, 30, 62], jnp.int32)
    q2 = jnp.asarray(r.normal(size=(B, KVH, G, HD)), jnp.float32)
    k1, v1, ks1, vs1 = at(layer, kpc, vpc, kps, vps)
    ref = decode_attention_paged_ref(q2, k1, v1, 0, pt, lens, scale=0.3,
                                     window=window, k_scale=ks1, v_scale=vs1)
    pl = decode_attention_paged_pallas(q2, kpc, vpc, layer, pt, lens,
                                       scale=0.3, window=window, k_scale=kps,
                                       v_scale=vps, interpret=True)
    lx = decode_attention_paged_lax(q2, kpc, vpc, layer, pt, lens, scale=0.3,
                                    window=window, k_scale=kps, v_scale=vps)
    bitexact(ref, pl)
    close(ref, lx)


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("mode", MODES)
def test_quant_decode_paged_mla_alias(mode, n_layers, layer):
    r = rng(6)
    kvpc, kvps = quantized_stack(r, n_layers, (P, PS, 1, RK), mode)
    pt = jnp.asarray(r.permutation(P - 1)[: B * NB].reshape(B, NB) + 1,
                     jnp.int32)
    q3 = jnp.asarray(r.normal(size=(B, 1, G, RK)), jnp.float32)
    lens = jnp.asarray([3, 30, 62], jnp.int32)
    kv1, s1 = at(layer, kvpc, kvps)
    ref = decode_attention_paged_ref(q3, kv1, kv1, 0, pt, lens, scale=0.3,
                                     v_width=8, k_scale=s1)
    pl = decode_attention_paged_pallas(q3, kvpc, kvpc, layer, pt, lens,
                                       scale=0.3, v_width=8, k_scale=kvps,
                                       interpret=True)
    lx = decode_attention_paged_lax(q3, kvpc, kvpc, layer, pt, lens,
                                    scale=0.3, v_width=8, k_scale=kvps)
    bitexact(ref, pl)
    close(ref, lx)


# -- prefill attention: quantized cache prefix + fp chunk ---------------------

@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("ring,window", [(False, None), (True, 48)])
def test_quant_prefill_parity(mode, ring, window):
    r = rng(7)
    q = jnp.asarray(r.normal(size=(B, KVH, T, G, HD)), jnp.float32)
    kx = jnp.asarray(r.normal(size=(B, T, KVH, HD)), jnp.float32)
    vx = jnp.asarray(r.normal(size=(B, T, KVH, HD)), jnp.float32)
    kc = jnp.asarray(r.normal(size=(B, C, KVH, HD)) * 2, jnp.float32)
    vc = jnp.asarray(r.normal(size=(B, C, KVH, HD)), jnp.float32)
    offs = jnp.asarray([0, 17, 60], jnp.int32)
    kcc, kcs = quant.quantize(kc, mode)
    vcc, vcs = quant.quantize(vc, mode)
    ref = prefill_attention_ref(q, kx, vx, kcc, vcc, offs, ring=ring,
                                window=window, scale=0.3, block_k=16,
                                k_scale=kcs, v_scale=vcs)
    pl = prefill_attention_pallas(q, kx, vx, kcc, vcc, offs, ring=ring,
                                  window=window, scale=0.3, block_k=16,
                                  k_scale=kcs, v_scale=vcs, interpret=True)
    lx = prefill_attention_lax(q, kx, vx, kcc, vcc, offs, ring=ring,
                               window=window, scale=0.3, k_scale=kcs,
                               v_scale=vcs)
    bitexact(ref, pl)
    close(ref, lx)


@pytest.mark.parametrize("mode", MODES)
def test_quant_prefill_mla_alias(mode):
    r = rng(8)
    kvx = jnp.asarray(r.normal(size=(B, T, 1, RK)), jnp.float32)
    kvc = jnp.asarray(r.normal(size=(B, C, 1, RK)), jnp.float32)
    q1 = jnp.asarray(r.normal(size=(B, 1, T, G, RK)), jnp.float32)
    offs = jnp.asarray([0, 17, 60], jnp.int32)
    kvcc, kvcs = quant.quantize(kvc, mode)
    ref = prefill_attention_ref(q1, kvx, kvx[..., :8], kvcc, kvcc[..., :8],
                                offs, scale=0.3, block_k=16, k_scale=kvcs,
                                v_scale=kvcs)
    pl = prefill_attention_pallas(q1, kvx, kvx, kvcc, kvcc, offs, scale=0.3,
                                  block_k=16, v_width=8, k_scale=kvcs,
                                  interpret=True)
    lx = prefill_attention_lax(q1, kvx, kvx, kvcc, kvcc, offs, scale=0.3,
                               v_width=8, k_scale=kvcs)
    bitexact(ref, pl)
    close(ref, lx)


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("window", [None, 24])
def test_quant_prefill_paged_parity(mode, window, n_layers, layer):
    r = rng(9)
    kpc, kps = quantized_stack(r, n_layers, (P, PS, KVH, HD), mode, 2)
    vpc, vps = quantized_stack(r, n_layers, (P, PS, KVH, HD), mode)
    pt = jnp.asarray(r.permutation(P - 1)[: B * NB].reshape(B, NB) + 1,
                     jnp.int32)
    kx = jnp.asarray(r.normal(size=(B, T, KVH, HD)), jnp.float32)
    vx = jnp.asarray(r.normal(size=(B, T, KVH, HD)), jnp.float32)
    q2 = jnp.asarray(r.normal(size=(B, KVH, T, G, HD)), jnp.float32)
    offs = jnp.asarray([0, 17, 55], jnp.int32)
    k1, v1, ks1, vs1 = at(layer, kpc, vpc, kps, vps)
    ref = prefill_attention_paged_ref(q2, kx, vx, k1, v1, 0, pt, offs,
                                      window=window, scale=0.3,
                                      k_scale=ks1, v_scale=vs1)
    pl = prefill_attention_paged_pallas(q2, kx, vx, kpc, vpc, layer, pt,
                                        offs, window=window, scale=0.3,
                                        k_scale=kps, v_scale=vps,
                                        interpret=True)
    lx = prefill_attention_paged_lax(q2, kx, vx, kpc, vpc, layer, pt, offs,
                                     window=window, scale=0.3,
                                     k_scale=kps, v_scale=vps)
    bitexact(ref, pl)
    close(ref, lx)


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("mode", MODES)
def test_quant_prefill_paged_mla_alias(mode, n_layers, layer):
    r = rng(10)
    kvx = jnp.asarray(r.normal(size=(B, T, 1, RK)), jnp.float32)
    kvpc, kvps = quantized_stack(r, n_layers, (P, PS, 1, RK), mode)
    pt = jnp.asarray(r.permutation(P - 1)[: B * NB].reshape(B, NB) + 1,
                     jnp.int32)
    q3 = jnp.asarray(r.normal(size=(B, 1, T, G, RK)), jnp.float32)
    offs = jnp.asarray([0, 17, 55], jnp.int32)
    kv1, s1 = at(layer, kvpc, kvps)
    ref = prefill_attention_paged_ref(q3, kvx, kvx, kv1, kv1, 0, pt, offs,
                                      scale=0.3, v_width=8, k_scale=s1)
    pl = prefill_attention_paged_pallas(q3, kvx, kvx, kvpc, kvpc, layer, pt,
                                        offs, scale=0.3, v_width=8,
                                        k_scale=kvps, interpret=True)
    lx = prefill_attention_paged_lax(q3, kvx, kvx, kvpc, kvpc, layer, pt,
                                     offs, scale=0.3, v_width=8,
                                     k_scale=kvps)
    bitexact(ref, pl)
    close(ref, lx)
