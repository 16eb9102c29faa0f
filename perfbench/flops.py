"""Operations and bytes that serving requires, from shapes and lengths.

Counted from the work each request needs, not from what a kernel or a
step happens to compute: padding rows, passenger rows of a batched
prefill chunk and pages read past a row's live length are not work.
Multiply-adds count 2 operations; weights and cached keys and values
are bfloat16 (2 bytes).

``c`` is a benchmark config (published key names).
"""
from __future__ import annotations

from typing import Dict

KV_BYTES = 2


def matmul_flops_per_token(c: Dict) -> float:
    """Projection and MLP operations of one token through every block."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kvh, ff = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["intermediate_size"])
    per_layer = d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff
    return 2.0 * c["num_hidden_layers"] * per_layer


def head_flops(c: Dict) -> float:
    """Logits of one position over the whole vocabulary."""
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def attn_flops(c: Dict, ctx: float) -> float:
    """Attention of one query over ``ctx`` keys, every layer: q.k and
    p.v, each ``heads * head_dim`` multiply-adds per key."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * ctx)


def attn_bytes(c: Dict, ctx: float, queries: int = 1) -> float:
    """Bytes an attention call must move for ``queries`` consecutive
    queries over ``ctx`` keys, every layer: the keys and values once,
    the queries read and the outputs written once."""
    L, kvh, h, hd = (c["num_hidden_layers"], c["num_key_value_heads"],
                     c["num_attention_heads"], c["head_dim"])
    kv = 2 * kvh * hd * ctx * KV_BYTES
    qo = 2 * h * hd * queries * KV_BYTES
    return float(L * (kv + qo))


def decode_work(c: Dict, prompt_len: int, served: int) -> Dict[str, float]:
    """Work of the decode steps of one request that served ``served``
    tokens after a prompt of ``prompt_len``: the first token comes from
    prefill; decode step ``i`` (1-based) feeds token ``i`` at position
    ``prompt_len + i - 1`` and attends ``prompt_len + i`` keys."""
    steps = max(0, served - 1)
    # sum of contexts prompt_len + i for i = 1..steps
    ctx_sum = steps * prompt_len + steps * (steps + 1) / 2
    per_tok = matmul_flops_per_token(c) + head_flops(c)
    return {"flops": steps * per_tok + attn_flops(c, ctx_sum),
            "attn_flops": attn_flops(c, ctx_sum),
            "attn_bytes": attn_bytes(c, ctx_sum, queries=steps),
            "tokens": steps}


def prefill_work(c: Dict, prompt_len: int, cached: int,
                 chunk: int) -> Dict[str, float]:
    """Work of prefilling positions ``cached .. prompt_len - 1`` (the
    first ``cached`` came from the prefix cache) in chunks of
    ``chunk``: every position attends itself and all before it; each
    chunk reads the keys and values before it and its own once; one row
    of logits gives the first token."""
    n = prompt_len - cached
    if n <= 0:
        return {"flops": 0.0, "attn_flops": 0.0, "attn_bytes": 0.0,
                "tokens": 0}
    # sum over positions p in [cached, prompt_len) of (p + 1)
    ctx_sum = (prompt_len * (prompt_len + 1) - cached * (cached + 1)) / 2
    bytes_ = 0.0
    start = cached
    while start < prompt_len:
        end = min(start + chunk, prompt_len)
        bytes_ += attn_bytes(c, end, queries=end - start)
        start = end
    return {"flops": n * matmul_flops_per_token(c) + head_flops(c)
            + attn_flops(c, ctx_sum),
            "attn_flops": attn_flops(c, ctx_sum),
            "attn_bytes": bytes_, "tokens": n}
