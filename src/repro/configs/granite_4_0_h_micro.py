"""Granite-4.0-H-Micro [hf:ibm-granite/granite-4.0-h-micro, config.json]:
a 3.19 B hybrid of Mamba-2 and GQA attention (``granitemoehybrid``, no
experts). 40 layers = 4 x (mamba x 5, attention, mamba x 4); attention has
no position embedding and scales scores by 1/64; Granite's embedding,
residual and logits multipliers; tied embeddings."""
from ..models.config import MambaConfig, ModelConfig

PERIOD = ("mamba",) * 5 + ("attn",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_head=64,
    d_ff=8192,                 # shared_intermediate_size
    vocab=100_352,
    activation="silu",
    norm_eps=1e-5,
    tie_embeddings=True,
    mamba=MambaConfig(d_state=128, d_conv=4, expand=2, head_dim=64, n_groups=1, chunk=256),
    layer_groups=((PERIOD, 4),),
    rope=False,                # position_embedding_type "nope"
    attn_scale=0.015625,       # attention_multiplier
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    # transformers' initializer_range default; with the tied head and the
    # embedding multiplier, a unit draw would make each position predict
    # its own token
    embed_std=0.02,
)
