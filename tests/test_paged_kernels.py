"""Paged kernel parity gates: bit-exact vs the ref.py twins.

Every paged kernel runs in interpret mode on CPU against its blockwise
oracle with *scrambled* page tables (physical pages deliberately out of
logical order, shared across no two rows) and must agree bitwise —
same contract the contiguous kernels already meet.  The lax fallbacks
are held to fp-reassociation tolerance, and an identity-layout
crosscheck pins the paged refs to the contiguous ones (same math, page
table == identity).

The kernels take a layer stack's pools (L, P, page_size, ...) and a
layer index.  Each gate runs on a stack of one (layer 0) and on a stack
of three at an inner layer, where the result must equal the one-pool
contract applied to ``pool[layer]`` bitwise, and a write must leave
every other layer, and the pages beside the layer's scratch page,
untouched.
"""
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.cache_update.ops as cu_ops
import repro.kernels.decode_attention.ops as da_ops
import repro.kernels.prefill_attention.ops as pf_ops
from repro.kernels.cache_update.ref import paged_cache_update_ref
from repro.kernels.decode_attention.ref import (decode_attention_paged_ref,
                                                decode_attention_ref)
from repro.kernels.prefill_attention.ref import (prefill_attention_paged_ref,
                                                 prefill_attention_ref)


def key(i):
    return jax.random.PRNGKey(i)


def scrambled_table(seed, b, nb, num_pages):
    """A (B, NB) page table of distinct physical pages, never page 0,
    deliberately out of logical order."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, num_pages))[:b * nb]
    return jnp.asarray(perm.reshape(b, nb), jnp.int32)


def gather_logical(pool, pt):
    """Logical (B, NB*page_size, ...) view of one (P, page_size, ...)
    pool through the page table."""
    b, nb = pt.shape
    ps = pool.shape[1]
    return jnp.take(pool, pt, axis=0).reshape(b, nb * ps, *pool.shape[2:])


# (layers in the stack, layer addressed): a stack of one, and an inner
# layer of three, whose scratch page 0 sits between two layers' pages.
STACKS = [(1, 0), (3, 1)]


def one_layer(pool, layer):
    """``pool[layer]`` as a stack of one — the one-pool contract."""
    return pool[layer:layer + 1]


# ---------------------------------------------------------------------------
# paged cache_update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("t,rest", [(1, (2, 8)), (8, (2, 8)), (8, (24,))])
def test_paged_cache_update_interpret_bitwise(t, rest, n_layers, layer):
    b, nb, ps = 3, 4, 4
    num_pages = b * nb + 1
    pool = jax.random.normal(key(0), (n_layers, num_pages, ps, *rest),
                             jnp.float32)
    new = jax.random.normal(key(1), (b, t, *rest), jnp.float32)
    pt = scrambled_table(2, b, nb, num_pages)
    starts = jnp.array([0, 5, nb * ps - t], jnp.int32)
    valids = jnp.array([t, max(t - 2, 0), t], jnp.int32)

    got = np.asarray(cu_ops.paged_cache_update(pool, layer, new, pt, starts,
                                               valids,
                                               impl="pallas_interpret"))
    want = np.asarray(paged_cache_update_ref(one_layer(pool, layer), 0, new,
                                             pt, starts, valids))[0]
    # page 0 is scratch: masked rows land there and its content is
    # undefined by contract — compare every real page bitwise.
    np.testing.assert_array_equal(got[layer, 1:], want[1:])
    np.testing.assert_array_equal(
        np.asarray(paged_cache_update_ref(pool, layer, new, pt, starts,
                                          valids))[layer, 1:], want[1:])
    # every other layer, the scratch page's neighbours included, is
    # left as it was
    others = [i for i in range(n_layers) if i != layer]
    np.testing.assert_array_equal(got[others], np.asarray(pool)[others])


def test_paged_cache_update_matches_contiguous_semantics():
    """Through an identity layout, the paged scatter must equal writing
    new[b, :valids[b]] at starts[b] of a contiguous (B, C, F) cache."""
    b, nb, ps, t, f = 2, 3, 4, 4, 6
    num_pages = b * nb + 1
    pt = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(b, nb)
    pool = jax.random.normal(key(3), (2, num_pages, ps, f), jnp.float32)
    new = jax.random.normal(key(4), (b, t, f), jnp.float32)
    starts = jnp.array([2, 7], jnp.int32)
    valids = jnp.array([4, 3], jnp.int32)

    out = paged_cache_update_ref(pool, 1, new, pt, starts, valids)
    got = gather_logical(out[1], pt)
    want = np.array(gather_logical(pool[1], pt))
    for i in range(b):
        s, v = int(starts[i]), int(valids[i])
        want[i, s:s + v] = np.asarray(new)[i, :v]
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(pool[0]))


@pytest.mark.parametrize("n_layers,layer", STACKS)
def test_paged_cache_update_masked_rows_leave_pages_untouched(n_layers,
                                                              layer):
    b, nb, ps, f = 2, 2, 4, 8
    num_pages = b * nb + 1
    pool = jax.random.normal(key(5), (n_layers, num_pages, ps, f),
                             jnp.float32)
    new = jax.random.normal(key(6), (b, 4, f), jnp.float32)
    pt = scrambled_table(7, b, nb, num_pages)
    out = np.asarray(cu_ops.paged_cache_update(pool, layer, new, pt,
                                               jnp.zeros(b, jnp.int32),
                                               jnp.zeros(b, jnp.int32),
                                               impl="pallas_interpret"))
    keep = np.ones(out.shape[:2], bool)
    keep[layer, 0] = False                 # the layer's scratch page
    np.testing.assert_array_equal(out[keep], np.asarray(pool)[keep])


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


def _decode_case(seed, b=3, nb=4, ps=16, kvh=2, g=4, hd=32, hdv=24,
                 alias=False, n_layers=1):
    # Same dimension class as the contiguous bitwise gates
    # (test_decode_attention.py): hdq=32, hdv=24, C=64, block=16,
    # G in {1, 4, 8}.  That is the regime where the CPU
    # interpret-mode matmul lowering agrees bitwise with the einsum
    # oracle — at e.g. G=2 XLA picks a different contraction order
    # and even the *contiguous* kernel/ref pair splits by ~1 ulp.
    num_pages = b * nb + 1
    q = jax.random.normal(key(seed), (b, kvh, g, hd), jnp.float32)
    k = jax.random.normal(key(seed + 1), (n_layers, num_pages, ps, kvh, hd),
                          jnp.float32)
    v = k if alias else jax.random.normal(
        key(seed + 2), (n_layers, num_pages, ps, kvh, hdv), jnp.float32)
    pt = scrambled_table(seed + 3, b, nb, num_pages)
    lens = jnp.array([0, nb * ps // 2, nb * ps - 1][:b], jnp.int32)
    return q, k, v, pt, lens


def one_layer_kv(k, v, layer):
    """The one-pool contract's k/v: ``[layer]`` as stacks of one, the
    same array twice where v aliases k."""
    k1 = one_layer(k, layer)
    return k1, (k1 if v is k else one_layer(v, layer))


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("softcap", [None, 30.0])
def test_paged_decode_interpret_bitwise(window, softcap, n_layers, layer):
    q, k, v, pt, lens = _decode_case(10, n_layers=n_layers)
    kw = dict(window=window, softcap=softcap,
              scale=1.0 / math.sqrt(32))
    got = da_ops.decode_attention_paged_pallas(q, k, v, layer, pt, lens,
                                               interpret=True, **kw)
    want = decode_attention_paged_ref(q, *one_layer_kv(k, v, layer), 0, pt,
                                      lens, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(decode_attention_paged_ref(q, k, v, layer, pt, lens,
                                              **kw)), np.asarray(want))


@pytest.mark.parametrize("n_layers,layer", STACKS)
def test_paged_decode_vwidth_alias_interpret_bitwise(n_layers, layer):
    q, k, v, pt, lens = _decode_case(20, alias=True, n_layers=n_layers)
    kw = dict(scale=0.5, v_width=24)
    got = da_ops.decode_attention_paged_pallas(q, k, v, layer, pt, lens,
                                               interpret=True, **kw)
    want = decode_attention_paged_ref(q, *one_layer_kv(k, v, layer), 0, pt,
                                      lens, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("window", [None, 13])
def test_paged_decode_lax_matches_ref(window, n_layers, layer):
    q, k, v, pt, lens = _decode_case(30, n_layers=n_layers)
    kw = dict(window=window, softcap=50.0, scale=0.3)
    got = da_ops.decode_attention_paged_lax(q, k, v, layer, pt, lens, **kw)
    want = decode_attention_paged_ref(q, *one_layer_kv(k, v, layer), 0, pt,
                                      lens, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_paged_decode_ref_equals_contiguous_ref_on_identity_layout():
    b, nb, ps = 2, 4, 16
    num_pages = b * nb + 1
    q, k, v, _, lens = _decode_case(40, b=b, nb=nb, ps=ps)
    pt = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(b, nb)
    k_log, v_log = gather_logical(k[0], pt), gather_logical(v[0], pt)
    got = decode_attention_paged_ref(q, k, v, 0, pt, lens, scale=0.4)
    want = decode_attention_ref(q, k_log, v_log, lens, ring=False,
                                scale=0.4, block_k=ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_decode_wrapper_layout():
    b, nb, ps, kvh, g, hd = 2, 3, 4, 2, 3, 8
    num_pages = b * nb + 1
    h = kvh * g
    q = jax.random.normal(key(50), (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(key(51), (2, num_pages, ps, kvh, hd), jnp.float32)
    v = jax.random.normal(key(52), (2, num_pages, ps, kvh, hd), jnp.float32)
    pt = scrambled_table(53, b, nb, num_pages)
    lens = jnp.array([3, 9], jnp.int32)
    out = da_ops.decode_attention_paged(q, k, v, 1, pt, lens, impl="lax")
    assert out.shape == (b, 1, h, hd)
    want = decode_attention_paged_ref(
        q.reshape(b, kvh, g, hd), k, v, 1, pt, lens)
    np.testing.assert_allclose(np.asarray(out).reshape(b, kvh, g, hd),
                               np.asarray(want), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# paged prefill attention
# ---------------------------------------------------------------------------


def _prefill_case(seed, b=3, nb=4, ps=16, t=16, kvh=2, g=4, hd=32,
                  hdv=24, alias=False, n_layers=1):
    # Same dimension class as the contiguous bitwise gates
    # (test_prefill_attention.py) — see the _decode_case note on G.
    num_pages = b * nb + 1
    q = jax.random.normal(key(seed), (b, kvh, t, g, hd), jnp.float32)
    kx = jax.random.normal(key(seed + 1), (b, t, kvh, hd), jnp.float32)
    vx = kx if alias else jax.random.normal(key(seed + 2),
                                            (b, t, kvh, hdv), jnp.float32)
    kc = jax.random.normal(key(seed + 3),
                           (n_layers, num_pages, ps, kvh, hd), jnp.float32)
    vc = kc if alias else jax.random.normal(
        key(seed + 4), (n_layers, num_pages, ps, kvh, hdv), jnp.float32)
    pt = scrambled_table(seed + 5, b, nb, num_pages)
    offs = jnp.array([0, 5, nb * ps - t][:b], jnp.int32)
    return q, kx, vx, kc, vc, pt, offs


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("softcap", [None, 20.0])
def test_paged_prefill_interpret_bitwise(window, softcap, n_layers, layer):
    q, kx, vx, kc, vc, pt, offs = _prefill_case(60, n_layers=n_layers)
    kw = dict(window=window, softcap=softcap,
              scale=1.0 / math.sqrt(32))
    got = pf_ops.prefill_attention_paged_pallas(q, kx, vx, kc, vc, layer, pt,
                                                offs, interpret=True, **kw)
    want = prefill_attention_paged_ref(q, kx, vx,
                                       *one_layer_kv(kc, vc, layer), 0, pt,
                                       offs, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(prefill_attention_paged_ref(q, kx, vx, kc, vc, layer, pt,
                                               offs, **kw)),
        np.asarray(want))


@pytest.mark.parametrize("n_layers,layer", STACKS)
def test_paged_prefill_vwidth_alias_interpret_bitwise(n_layers, layer):
    q, kx, vx, kc, vc, pt, offs = _prefill_case(70, alias=True,
                                                n_layers=n_layers)
    kw = dict(scale=0.5, v_width=24)
    got = pf_ops.prefill_attention_paged_pallas(q, kx, vx, kc, vc, layer, pt,
                                                offs, interpret=True, **kw)
    want = prefill_attention_paged_ref(q, kx, vx,
                                       *one_layer_kv(kc, vc, layer), 0, pt,
                                       offs, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_layers,layer", STACKS)
@pytest.mark.parametrize("window", [None, 13])
def test_paged_prefill_lax_matches_ref(window, n_layers, layer):
    q, kx, vx, kc, vc, pt, offs = _prefill_case(80, n_layers=n_layers)
    kw = dict(window=window, softcap=25.0, scale=0.3)
    got = pf_ops.prefill_attention_paged_lax(q, kx, vx, kc, vc, layer, pt,
                                             offs, **kw)
    want = prefill_attention_paged_ref(q, kx, vx,
                                       *one_layer_kv(kc, vc, layer), 0, pt,
                                       offs, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_paged_prefill_ref_equals_contiguous_ref_on_identity_layout():
    b, nb, ps, t = 2, 4, 16, 16
    num_pages = b * nb + 1
    q, kx, vx, kc, vc, _, offs = _prefill_case(90, b=b, nb=nb, ps=ps, t=t)
    pt = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(b, nb)
    got = prefill_attention_paged_ref(q, kx, vx, kc, vc, 0, pt, offs,
                                      scale=0.4)
    want = prefill_attention_ref(q, kx, vx, gather_logical(kc[0], pt),
                                 gather_logical(vc[0], pt), offs, ring=False,
                                 scale=0.4, block_k=ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_prefill_wrapper_layout():
    b, nb, ps, t, kvh, g, hd = 2, 3, 4, 4, 2, 2, 8
    num_pages = b * nb + 1
    h = kvh * g
    q = jax.random.normal(key(95), (b, t, h, hd), jnp.float32)
    kx = jax.random.normal(key(96), (b, t, kvh, hd), jnp.float32)
    vx = jax.random.normal(key(97), (b, t, kvh, hd), jnp.float32)
    kc = jax.random.normal(key(98), (2, num_pages, ps, kvh, hd), jnp.float32)
    vc = jax.random.normal(key(99), (2, num_pages, ps, kvh, hd), jnp.float32)
    pt = scrambled_table(100, b, nb, num_pages)
    offs = jnp.array([0, 7], jnp.int32)
    out = pf_ops.prefill_attention_paged(q, kx, vx, kc, vc, 1, pt, offs,
                                         impl="lax")
    assert out.shape == (b, t, h, hd)
    want = prefill_attention_paged_ref(
        q.reshape(b, t, kvh, g, hd).transpose(0, 2, 1, 3, 4),
        kx, vx, kc, vc, 1, pt, offs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want).transpose(0, 2, 1, 3, 4)
        .reshape(b, t, h, hd), rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# headless pools (one kv head stored without its axis: the MLA latent)
# and query blocks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_layers,layer", STACKS)
def test_paged_decode_headless_pool_matches_head_axis(n_layers, layer):
    """A (L, P, page_size, hd) pool read in place gives, bitwise, what
    the same pool viewed with a kv-head axis of one gives, in the
    kernel (interpret), its lax twin and the wrapper."""
    q, k, _, pt, lens = _decode_case(110, kvh=1, g=8, alias=True,
                                     n_layers=n_layers)
    kw = dict(scale=0.5, v_width=24)
    pool = k[..., 0, :]
    for fn in (functools.partial(da_ops.decode_attention_paged_pallas,
                                 interpret=True),
               da_ops.decode_attention_paged_lax):
        got = fn(q, pool, pool, layer, pt, lens, **kw)
        want = fn(q, k, k, layer, pt, lens, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    b, _, g, hd = q.shape
    out = da_ops.decode_attention_paged(q.reshape(b, 1, g, hd), pool, pool,
                                        layer, pt, lens, impl="lax", **kw)
    assert out.shape == (b, 1, g, 24)


@pytest.mark.parametrize("n_layers,layer", STACKS)
def test_paged_prefill_headless_pool_matches_head_axis(n_layers, layer):
    q, kx, _, kc, _, pt, offs = _prefill_case(120, kvh=1, g=8, alias=True,
                                              n_layers=n_layers)
    kw = dict(scale=0.5, v_width=24)
    pool = kc[..., 0, :]
    for fn in (functools.partial(pf_ops.prefill_attention_paged_pallas,
                                 interpret=True),
               pf_ops.prefill_attention_paged_lax):
        got = fn(q, kx, kx, pool, pool, layer, pt, offs, **kw)
        want = fn(q, kx, kx, kc, kc, layer, pt, offs, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", [None, 11])
def test_paged_prefill_query_blocks_interpret_bitwise(monkeypatch, window):
    """A chunk whose rows overflow a grid step's budget runs in blocks
    of queries, each sweeping the prefix and the chunk: the oracle's
    result over the whole chunk, to 1 ulp (the CPU interpret-mode
    matmul picks its contraction order by the block's row count)."""
    pa = importlib.import_module(
        "repro.kernels.prefill_attention.prefill_attention")
    q, kx, vx, kc, vc, pt, offs = _prefill_case(130, n_layers=3)
    _, kvh, t, g, hd = q.shape
    monkeypatch.setattr(pa, "QUERY_BLOCK_ELEMS",
                        t // 4 * kvh * g * (hd + vc.shape[-1]))
    assert pa.query_block(t, kvh, g, hd, vc.shape[-1]) == t // 4
    kw = dict(window=window, scale=1.0 / math.sqrt(32))
    got = pf_ops.prefill_attention_paged_pallas(q, kx, vx, kc, vc, 1, pt,
                                                offs, interpret=True, **kw)
    want = prefill_attention_paged_ref(q, kx, vx, kc, vc, 1, pt, offs, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_query_block_keeps_dense_chunks_whole():
    query_block = importlib.import_module(
        "repro.kernels.prefill_attention.prefill_attention").query_block
    assert query_block(32, 8, 2, 128, 128) == 32      # qwen3-0.6b
    assert query_block(32, 16, 1, 128, 128) == 32     # olmo-1b
    assert query_block(32, 1, 128, 640, 512) == 8     # deepseek-v3 MLA
