"""The dense family (Qwen3 / OLMo style decoders), as the harness needs
it beside the plain reference: the sizes the program's config must
state, the program's parameter tree, and the work serving requires.

A configuration picks its family by its ``reference`` key, as it picks
``reference/<reference>.py``; a new family brings a file of its own
with these six functions.  The reference is written from the published
description; this file knows the program's tree.

Operations and bytes are counted from the work each request needs, not
from what a kernel or a step happens to compute.  Multiply-adds count 2
operations; weights and cached keys and values are bfloat16 (2 bytes).
``c`` is a benchmark config (published key names).
"""
from __future__ import annotations

from typing import Dict

KV_BYTES = 2


def stated(c: Dict) -> Dict:
    """Program-config attribute -> the value the file states."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "qk_norm": c["qk_norm"], "tie_embeddings": True,
            "norm_type": ("rmsnorm" if c["norm"] == "rmsnorm"
                          else "layernorm_nonparam")}


def layout(c: Dict, w: Dict) -> Dict:
    """Benchmark weights ``w`` (``reference.dense`` names) as the
    program's parameter tree.  Call under ``jax.jit``."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    mixer = {"wq": w["wq"].reshape(L, d, h, hd),
             "wk": w["wk"].reshape(L, d, kvh, hd),
             "wv": w["wv"].reshape(L, d, kvh, hd),
             "wo": w["wo"].reshape(L, h, hd, d)}
    if c["qk_norm"]:
        mixer.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    norm = (lambda n: {"scale": w[n]}) if c["norm"] == "rmsnorm" \
        else (lambda n: {})
    return {"embed": {"embedding": w["embed"]},
            "final_norm": norm("final_norm"),
            "units": {"r0": {"norm_1": norm("attn_norm"),
                             "norm_2": norm("mlp_norm"),
                             "mixer": mixer,
                             "ffn": {"w_gate": w["w_gate"],
                                     "w_up": w["w_up"],
                                     "w_down": w["w_down"]}}}}


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
