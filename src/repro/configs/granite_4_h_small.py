"""granite-4.0-h-small [hybrid_moe] — Granite 4.0-H Small (32B-A9B): 40
layers in periods of 10, each period 9 Mamba-2 layers and one GQA
attention layer without position encoding (index 5 of the period); every
layer's mixer is followed by 72 SwiGLU experts (top-10) beside a shared
SwiGLU expert; Granite's embedding, attention, residual and logits
multipliers. [hf:ibm-granite/granite-4.0-h-small config.json]
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid_moe",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=0,
    vocab_size=100352,
    activation="swiglu",
    layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    position_embedding="nope",
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    # intermediate_size (768) read as the expert width, as the catalog notes
    moe=MoEConfig(num_experts=72, experts_per_token=10, d_ff_expert=768,
                  d_ff_shared=1536),
    ssm=SSMConfig(d_state=128, expand=2, num_heads=128, head_dim=64,
                  n_groups=1, conv_width=4, chunk=256),
    # served through the paged engine only; not one of the dry-run cells
    supported_shapes=(),
    source="hf:ibm-granite/granite-4.0-h-small",
)
