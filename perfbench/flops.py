"""Operations and bytes that serving requires, from shapes and lengths.

Counted from the work each request needs, not from what a kernel or a
step happens to compute: padding rows, passenger rows of a batched
prefill chunk and pages read past a row's live length are not work.

``family`` is the config's module ``families/<reference>.py`` (the
run's ``Run.family``), which counts the work of one token, one row of
logits and one attention call; ``c`` is a benchmark config (published
key names).  This module sums those counts over a request's steps.
"""
from __future__ import annotations

from typing import Dict


def decode_work(family, c: Dict, prompt_len: int,
                served: int) -> Dict[str, float]:
    """Work of the decode steps of one request that served ``served``
    tokens after a prompt of ``prompt_len``: the first token comes from
    prefill; decode step ``i`` (1-based) feeds token ``i`` at position
    ``prompt_len + i - 1`` and attends ``prompt_len + i`` keys."""
    steps = max(0, served - 1)
    # sum of contexts prompt_len + i for i = 1..steps
    ctx_sum = steps * prompt_len + steps * (steps + 1) / 2
    per_tok = family.matmul_flops_per_token(c) + family.head_flops(c)
    return {"flops": steps * per_tok + family.attn_flops(c, ctx_sum),
            "attn_flops": family.attn_flops(c, ctx_sum),
            "attn_bytes": family.attn_bytes(c, ctx_sum, queries=steps),
            "tokens": steps}


def prefill_work(family, c: Dict, prompt_len: int, cached: int,
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
        bytes_ += family.attn_bytes(c, end, queries=end - start)
        start = end
    return {"flops": n * family.matmul_flops_per_token(c)
            + family.head_flops(c) + family.attn_flops(c, ctx_sum),
            "attn_flops": family.attn_flops(c, ctx_sum),
            "attn_bytes": bytes_, "tokens": n}
