"""deepseek-v2-lite — MoE decoder with Multi-head Latent Attention, as one
chip of an expert-parallel group serves it.

[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json] 27L
d_model=2048 16H vocab=102400; MLA kv_lora=512, no q_lora (q is one plain
projection), rope_dim=64, nope=128, v=128; YaRN RoPE (factor 40 over 4096
positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707);
layer 0 dense (d_ff 10944), then 26 MoE layers of 64 routed experts
(width 1408, top-6, softmax, no renormalisation, scaling 1) and 2 shared.

Every routed expert is held here, as the published model has them. Which
experts a chip holds is the deployment's choice (``MoEConfig.held_experts``):
one rank of DeepSeek's EP8 inference layout, attention data-parallel and
the routed experts split over 8 chips, holds 8 of the 64 and everything
else whole.
"""
from repro.configs.base import ArchConfig, MoEConfig, MLAConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
    d_ff=1408, vocab=102400,
    attn_type="mla",
    mla=MLAConfig(q_lora=None, kv_lora=512, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  first_k_dense=1, d_ff_dense=10944, renormalize=False,
                  routed_scaling_factor=1.0),
    rope_theta=1e4,
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
)

SMOKE = CONFIG.replace(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=32,
    vocab=256,
    mla=MLAConfig(q_lora=None, kv_lora=32, rope_head_dim=8, nope_head_dim=16,
                  v_head_dim=16),
    moe=MoEConfig(n_experts=16, top_k=3, d_expert=32, n_shared=2,
                  first_k_dense=1, d_ff_dense=128, renormalize=False,
                  routed_scaling_factor=1.0),
    rope_scaling=YarnScaling(factor=40.0, original_max_position_embeddings=8,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    dtype="float32",
)
