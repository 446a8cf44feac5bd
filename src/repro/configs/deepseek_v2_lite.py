"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512, no q-LoRA) with YaRN
rotary scaling, then 26 MoE layers of 64 routed experts (top-6, softmax,
greedy, weights not renormalised) and 2 shared experts after one dense
layer (d_ff 10944).

Source: https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
(arXiv:2405.04434).  `d_ff` is `moe_intermediate_size`, the per-expert
width; `dense_d_ff` is `intermediate_size`, the first layer's.  Every
routed expert is held (`n_held` 0); a deployment over several chips gives
each its share with `first_expert` and `n_held`.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    act="silu",
    norm_eps=1e-6,
    rope_theta=1e4,
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared_experts=2, d_expert=1408,
                  dense_residual=False, first_dense_layers=1, dense_d_ff=10944,
                  norm_topk_prob=False, routed_scaling_factor=1.0),
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
)
