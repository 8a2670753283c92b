"""granite-4.0-h-micro [hybrid] — 40L d2048, Mamba-2 + attention 9:1,
ff8192 (shared MLP in every layer), v100352, tied embeddings.

``layer_types`` place attention at layers 5, 15, 25, 35: the period is
mamba×5, attention, mamba×4. Mamba-2: 64 heads × 64 (d_inner 4096,
expand 2), d_state 128, 1 group, causal conv 4 with bias. Attention: GQA
32 q over 8 kv heads of 64, NoPE, scale ``attention_multiplier`` 1/64.
Granite scalars: embeddings × 12, residual branches × 0.22, logits / 8;
RMSNorm eps 1e-5. Sub-quadratic → runs long_500k.
[hf:ibm-granite/granite-4.0-h-micro config.json]
"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,                  # shared_intermediate_size
    vocab_size=100352,
    norm="rmsnorm",
    norm_eps=1e-5,
    activation="silu_glu",
    block_pattern=("mamba",) * 5 + ("attn",) + ("mamba",) * 4,
    use_rope=False,
    attention_multiplier=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    tie_embeddings=True,
    conv_width=4,
    mamba_heads=64,
    mamba_head_dim=64,
    mamba_d_state=128,
    mamba_groups=1,
    # 36 of the 40 layers keep O(1) state per sequence; the 4 NoPE
    # attention layers decode in time linear in the context
    subquadratic=True,
))
