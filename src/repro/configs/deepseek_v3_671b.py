"""deepseek-v3-671b — DeepSeek-V3 [arXiv:2412.19437].

Assigned: 61L d_model=7168 128H d_ff=2048 vocab=129280, MoE 256e top-8,
MLA, 1 shared + 256 routed, MTP.  First 3 layers dense (ff 18432).
Routing and positions as the published config.json states them:
sigmoid scores, ``noaux_tc`` choice over 8 groups of which the best 4
are kept, normalized gates scaled by 2.5; YaRN RoPE (factor 40 over
4096 original positions), which also scales the softmax.
"""
import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18432,                      # leading dense layers' ffn
    vocab_size=129280,
    head_dim=128,
    attention="mla",
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128),
    rope_theta=10000.0,
    rope_scaling=YarnConfig(factor=40.0,
                            original_max_position_embeddings=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    moe=MoEConfig(num_experts=256, top_k=8, ff_dim=2048,
                  num_shared_experts=1, capacity_factor=1.25,
                  first_dense_layers=3, dense_ff_dim=18432,
                  scoring_func="sigmoid", topk_method="noaux_tc",
                  n_group=8, topk_group=4, routed_scaling_factor=2.5,
                  norm_topk_prob=True),
    mtp=True,
    mtp_loss_weight=0.3,
    param_dtype="bfloat16",
    optimizer="adafactor",
)

REDUCED = dataclasses.replace(
    CONFIG,
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
    vocab_size=256, head_dim=16,
    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=2, ff_dim=32, num_shared_experts=1,
                  capacity_factor=1.25, first_dense_layers=1,
                  dense_ff_dim=128, scoring_func="sigmoid",
                  topk_method="noaux_tc", n_group=2, topk_group=1,
                  routed_scaling_factor=2.5, norm_topk_prob=True),
    loss_chunk=0, attn_chunk=64, ssm_chunk=16,
)
