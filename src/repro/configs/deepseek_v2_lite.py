"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, config.json]: 15.7 B
parameters, 2.4 B active. 27 layers of multi-head latent attention (16
heads, no q LoRA; a 512-wide latent and one 64-wide rotary key per
position; YaRN rotary, factor 40 over 4,096); layer 0 a dense SwiGLU of
10,944, layers 1-26 mixtures of 64 routed SwiGLU experts of 1,408 (softmax
router, greedy top-6, weights not renormalised, scale 1) and 2 shared
experts (one SwiGLU of 2 x 1,408). Untied embeddings.

The expert share of one chip of a deployment in which 8 chips share each
expert layer (expert parallel): rank 0 holds experts 0-7 and computes their
part of each expert layer; attention, the dense layer, the shared experts,
the embedding and the head are whole on every chip."""
from ..models.config import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10_944,               # intermediate_size: the dense layer's MLP
    vocab=102_400,
    activation="silu",
    rope_theta=10_000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0, beta_slow=1.0,
                    mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(
        num_experts=64, top_k=6, d_ff_expert=1408,
        d_ff_shared=2 * 1408,  # n_shared_experts x moe_intermediate_size
        impl="dropless", norm_topk_prob=False, first_k_dense=1,
        ep_size=8, ep_rank=0,
    ),
)
