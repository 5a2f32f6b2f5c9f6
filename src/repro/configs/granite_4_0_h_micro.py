"""granite-4.0-h-micro [mamba_attn] — Mamba2 layers with GQA attention at
every tenth layer (5, 15, 25, 35).  40L d_model=2048, attention 32H over 8
KV heads of 64 with no positional encoding, Mamba2 64 heads of 64 with
d_state=128 and one group, SiLU-gated MLP 8192 on every layer, vocab=100352
tied; residual branches scaled by 0.22, embedding by 12, logits divided by
8, softmax scale 1/64.  [hf:ibm-granite/granite-4.0-h-micro]
"""
from repro.configs.base import ArchConfig

_GRANITE = dict(
    family="mamba_attn",
    rotary_pct=0.0,  # position_embedding_type "nope"
    tie_embeddings=True,
    ssm_expand=2,
    ssm_conv=4,
    ssm_conv_bias=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.015625,
    logits_scaling=8.0,
)

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_ff=8192,
    vocab=100_352,
    ssm_state=128,
    ssm_head_dim=64,
    attn_layer_period=10,
    attn_layer_offset=5,
    **_GRANITE,
)

# one whole period of four layers: Mamba2, attention, Mamba2, Mamba2
SMOKE_CONFIG = ArchConfig(
    name="granite-4.0-h-micro-smoke",
    n_layers=4,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab=256,
    ssm_state=16,
    ssm_head_dim=16,
    attn_layer_period=4,
    attn_layer_offset=1,
    **_GRANITE,
)
