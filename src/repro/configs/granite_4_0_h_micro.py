"""granite-4.0-h-micro: 40L d=2048 hybrid — 36 Mamba2 layers (64 heads of
64, d_state 128, one group) and 4 GQA attention layers (32H, kv=8, hd 64,
no position embedding) at offset 5 of every 10, each layer with its own
SwiGLU MLP (d_ff 8192); vocab 100352, tied; embedding x12, residual
branches x0.22, attention scale 1/64, logits / 8.
[hf:ibm-granite/granite-4.0-h-micro config.json]"""
from repro.models.config import ModelConfig, SSMConfig, register

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", kind="hybrid", n_layers=40, d_model=2048,
    n_heads=32, n_kv_heads=8, d_ff=8192, vocab=100352, head_dim=64,
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256),
    attn_every=10, attn_offset=5, rope=False, norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0,
)
# two periods of (Mamba2, attention, Mamba2), grouped queries, and every
# multiplier away from 1 (the attention scale away from 1/sqrt(hd) too).
# The embedding multiplier is below 1: at 12 a 64-wide embedding outweighs
# every residual branch, and random weights then repeat the input token
# whatever the history
SMOKE = ModelConfig(
    name="granite-4.0-h-micro-smoke", kind="hybrid", n_layers=6, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab=256, head_dim=16,
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, conv_width=4, chunk=16),
    attn_every=3, attn_offset=1, rope=False, norm_eps=1e-5,
    embedding_multiplier=0.5, residual_multiplier=0.22,
    attention_multiplier=0.0625, logits_scaling=8.0,
    param_dtype="float32", compute_dtype="float32",
)
register(CONFIG, SMOKE)
