"""DeepSeek-V2-Lite [arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite] —
27L d2048 16H MLA (no query LoRA, kv_lora 512, qk 128+64, v 128), YaRN
rotary (factor 40 over 4096 positions), layer 0 a dense SwiGLU of 10944,
then 64 routed experts (ff 1408) top-6 with softmax scores, no
renormalisation, and 2 shared experts; RMSNorm eps 1e-6, vocab 102400."""

from repro.configs.base import ModelConfig, MoEConfig, MLAConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,          # the dense layer's SwiGLU
    vocab=102400,
    attention="mla",
    head_dim=192,        # qk_nope 128 + qk_rope 64
    rope="rope",
    rope_theta=10000.0,
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    norm="rmsnorm",
    norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408, shared_experts=2,
                  norm_topk_probs=False, layer_period=1),
    first_dense_layers=1,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab=256,
    attention="mla",
    head_dim=24,
    rope="rope",
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    norm="rmsnorm",
    norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32, shared_experts=2,
                  norm_topk_probs=False, layer_period=1, capacity_factor=8.0),
    first_dense_layers=1,
    param_dtype="float32",
    compute_dtype="float32",
)
