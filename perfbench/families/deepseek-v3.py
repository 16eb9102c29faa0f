"""The DeepSeek-V3 family (multi-head latent attention, sigmoid-routed
sparse experts), as the harness needs it beside the plain reference:
the sizes the program's config must state, the program's parameter
tree, and the work serving requires.

Operations and bytes are counted from the work each request needs, as
for the dense family (``families/dense.py``).  Attention is counted in
the absorbed form the program serves, for prefill and decode alike: a
query moves into the latent space, so each key costs, per head and
layer, 2 * (576 + 512) operations (``[q_lat | q_rope] . [latent |
k_rope]`` and ``p . latent``) and is read once as one 576-wide bf16
latent row (1152 bytes) for all heads.  The projections count the
absorption's two per-head matmuls (``q_nope W_UK``, ``ctx W_UV``) in
place of the non-absorbed key and value projections.  Routed experts
count the expected ``num_experts_per_tok * held / n_routed_experts``
assignments a token has to the experts held here (8 * 8 / 256 = 0.25),
the shared expert and the router every token.  ``c`` is a benchmark
config (published key names).
"""
from __future__ import annotations

from typing import Dict

KV_BYTES = 2


def stated(c: Dict) -> Dict:
    """Program-config attribute -> the value the file states.  The
    nested groups (latent attention, experts and routing, YaRN) are
    compared whole, so a program without a routing or YaRN field fails
    here, before it serves another model (a program whose config has
    no such fields fails at the import, inside ``model_config``)."""
    from repro.configs.base import MLAConfig, MoEConfig, YarnConfig
    dep, rs = c["deployment"], c["rope_scaling"]
    return {
        "num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_kv_heads": c["num_key_value_heads"],
        "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
        "tie_embeddings": c["tie_word_embeddings"], "attention": "mla",
        "norm_type": "rmsnorm", "act": c["hidden_act"], "mtp": False,
        "d_ff": c["intermediate_size"],
        "mla": MLAConfig(q_lora_rank=c["q_lora_rank"],
                         kv_lora_rank=c["kv_lora_rank"],
                         qk_nope_head_dim=c["qk_nope_head_dim"],
                         qk_rope_head_dim=c["qk_rope_head_dim"],
                         v_head_dim=c["v_head_dim"]),
        "moe": MoEConfig(
            num_experts=dep["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            ff_dim=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            first_dense_layers=c["first_k_dense_replace"],
            dense_ff_dim=c["intermediate_size"],
            every_k_layers=c["moe_layer_freq"],
            scoring_func=c["scoring_func"], topk_method=c["topk_method"],
            n_group=c["n_group"], topk_group=c["topk_group"],
            routed_scaling_factor=c["routed_scaling_factor"],
            norm_topk_prob=c["norm_topk_prob"],
            held_experts=c["n_routed_experts"],
            first_held_expert=dep["first_held_expert"]),
        "rope_scaling": YarnConfig(
            factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])}


def layout(c: Dict, w: Dict) -> Dict:
    """Benchmark weights ``w`` (``reference/deepseek-v3.py`` names) as
    the program's parameter tree: the dense layers unstacked under
    ``prefix``, the MoE layers stacked under ``units``.  Call under
    ``jax.jit``."""
    nd = c["first_k_dense_replace"]

    def block(g, sel):
        get = lambda n: sel(w[f"{g}.{n}"])
        mixer = {n: get(n) for n in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                     "kv_norm", "wk_b", "wv_b", "wo")}
        if g == "d":
            ffn = {n: get(n) for n in ("w_gate", "w_up", "w_down")}
        else:
            ffn = {n: get(n) for n in ("router", "router_bias", "w_gate",
                                       "w_up", "w_down")}
            ffn["shared"] = {"w_gate": get("s_gate"), "w_up": get("s_up"),
                             "w_down": get("s_down")}
        return {"norm_1": {"scale": get("attn_norm")},
                "norm_2": {"scale": get("mlp_norm")},
                "mixer": mixer, "ffn": ffn}

    units = block("m", lambda a: a)
    if c["num_hidden_layers"] - nd == 1:
        units = block("m", lambda a: a[0])
    return {"embed": {"embedding": w["embed"], "lm_head": w["head"]},
            "final_norm": {"scale": w["final_norm"]},
            "prefix": {f"l{i}": block("d", lambda a, i=i: a[i])
                       for i in range(nd)},
            "units": {"r0": units}}


def _latent_width(c: Dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def matmul_flops_per_token(c: Dict) -> float:
    """Projection and FFN operations of one token through every block."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    ql, kvl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    mla = (d * ql + ql * h * (nope + c["qk_rope_head_dim"])
           + d * _latent_width(c) + h * nope * kvl + h * kvl * vd
           + h * vd * d)
    nd = c["first_k_dense_replace"]
    nm = c["num_hidden_layers"] - nd
    e = c["moe_intermediate_size"]
    routed = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / c["deployment"]["n_routed_experts"])
    moe = (d * c["deployment"]["n_routed_experts"]
           + 3 * d * e * (c["n_shared_experts"] + routed))
    dense = 3 * d * c["intermediate_size"]
    return 2.0 * (c["num_hidden_layers"] * mla + nd * dense + nm * moe)


def head_flops(c: Dict) -> float:
    """Logits of one position over the vocabulary held here."""
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def attn_flops(c: Dict, ctx: float) -> float:
    """Absorbed attention of one query over ``ctx`` keys, every layer."""
    return (2.0 * (_latent_width(c) + c["kv_lora_rank"])
            * c["num_attention_heads"] * c["num_hidden_layers"] * ctx)


def attn_bytes(c: Dict, ctx: float, queries: int = 1) -> float:
    """Bytes an attention call must move for ``queries`` consecutive
    queries over ``ctx`` keys, every layer: each key's latent row once
    (shared by all heads), the absorbed queries read and the latent
    outputs written once."""
    h, w = c["num_attention_heads"], _latent_width(c)
    kv = w * ctx * KV_BYTES
    qo = h * (w + c["kv_lora_rank"]) * queries * KV_BYTES
    return float(c["num_hidden_layers"] * (kv + qo))
