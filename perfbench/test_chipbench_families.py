"""The family files at full size, without a chip: the dense family's
work counts pinned to the values the benchmark's readers have used
since its first cells, and every configuration's weights laid out as
the program's own tree (``jax.eval_shape`` only; nothing is allocated).
"""
import jax
import pytest

import conftest
import flops
import harness
import program

# matmul_flops_per_token, head_flops, attn_flops(c, 1000),
# attn_bytes(c, 1000, 1), decode_work(c, 700, 300), prefill_work(c, 900,
# 33, 32): flops and attn_bytes of the last two.
PINNED = {
    "qwen3-0.6b": (880803840, 311164928, 229376000, 114917376,
                   (414694572032, 29216538624), (856839913472, 1791541248)),
    "olmo-1b": (2147483648, 206045184, 131072000, 131203072,
                (737017069568, 33351139328), (1915143979008, 1933836288)),
}


def full_config(name):
    entry = {c["name"]: c for c in conftest.benchmark()["configs"]}[name]
    return harness.load_json(harness.CHECKOUT / entry["file"])


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_counts_are_pinned(name):
    c = full_config(name)
    fam = harness.family(harness.HERE, c)
    mm, head, attn, byt, dec, pre = PINNED[name]
    assert fam.matmul_flops_per_token(c) == mm
    assert fam.head_flops(c) == head
    assert fam.attn_flops(c, 1000) == attn
    assert fam.attn_bytes(c, 1000, 1) == byt
    d = flops.decode_work(fam, c, 700, 300)
    assert (d["flops"], d["attn_bytes"]) == dec
    p = flops.prefill_work(fam, c, 900, 33, 32)
    assert (p["flops"], p["attn_bytes"]) == pre


@pytest.mark.parametrize(
    "name", [c["name"] for c in conftest.benchmark()["configs"]])
def test_full_size_layout_is_the_programs(name):
    c = full_config(name)
    fam = harness.family(harness.HERE, c)
    ref = harness.reference(harness.HERE, c)
    cfg = program.model_config(c, fam)
    params = jax.eval_shape(lambda k: fam.layout(c, ref.make_weights(c, k)),
                            ref.key_from_seed(2**31 + 11))
    program.check_layout(cfg, params)
