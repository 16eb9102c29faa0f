"""Operation and byte counts against values worked out by hand."""
import pytest

import flops
import harness

# d 8, 2 heads of 4, 1 kv head, ff 16, vocab 10, 2 layers
C = dict(hidden_size=8, intermediate_size=16, num_hidden_layers=2,
         num_attention_heads=2, num_key_value_heads=1, head_dim=4,
         vocab_size=10, reference="dense")
F = harness.family(harness.HERE, C)


def test_matmul_and_head():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64+64+64+384
    assert F.matmul_flops_per_token(C) == 2 * 2 * 576
    assert F.head_flops(C) == 2 * 8 * 10


def test_attention_counts():
    # q.k and p.v: 2 heads * 4 dims * 2 ops each, per key, 2 layers
    assert F.attn_flops(C, 5) == 4 * 2 * 2 * 4 * 5
    # keys+values of 5 positions (1 kv head, 4 dims, 2 bytes) + one
    # query and one output (2 heads * 4 dims * 2 bytes), 2 layers
    assert F.attn_bytes(C, 5) == 2 * (2 * 4 * 5 * 2 + 2 * 8 * 2)


def test_decode_work_sums_live_contexts():
    # prompt 3, served 3: decode steps feed tokens 1, 2 at contexts 4, 5
    w = flops.decode_work(F, C, 3, 3)
    assert w["tokens"] == 2
    assert w["attn_flops"] == F.attn_flops(C, 9)
    assert w["flops"] == 2 * (2 * 2 * 576 + 160) + F.attn_flops(C, 9)
    assert w["attn_bytes"] == F.attn_bytes(C, 9, queries=2)
    assert flops.decode_work(F, C, 3, 1)["flops"] == 0


def test_prefill_work_skips_cached_positions_and_chunks_bytes():
    # prompt 5, 2 cached, chunk 2: positions 2,3,4 attend 3,4,5 keys;
    # chunks [2,4) and [4,5) read 4 and 5 keys
    w = flops.prefill_work(F, C, 5, 2, 2)
    assert w["tokens"] == 3
    assert w["attn_flops"] == F.attn_flops(C, 12)
    assert w["attn_bytes"] == (F.attn_bytes(C, 4, queries=2)
                               + F.attn_bytes(C, 5, queries=1))
    assert w["flops"] == pytest.approx(
        3 * 2 * 2 * 576 + 160 + F.attn_flops(C, 12))
    assert flops.prefill_work(F, C, 4, 4, 2)["flops"] == 0
