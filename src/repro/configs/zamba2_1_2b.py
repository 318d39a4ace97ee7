"""Zamba2-1.2B (hybrid Mamba2 + shared attention).  [arXiv:2411.15242;
huggingface.co/Zyphra/Zamba2-1.2B]
38 Mamba2 layers, d_model=2048 (d_inner 4096, 64 SSM heads of 64, state
64, conv 4, chunk 256), vocab 32000 tied.  One SHARED transformer block
runs before the Mamba2 layer at ids 6, 12, ..., 36 (six sites): attention
over [residual, embedding] (width 4096, 32 heads of 128, kv 32, scale
(128/2)^-1/2, RoPE theta 10000) and an exact-GELU gated MLP (d_ff 8192),
with per-site rank-128 adapters on q, k, v and gate_up and a per-site
2048x2048 `linear` (models/hybrid.py).  max_position_embeddings 4096.
1,205,078,912 parameters stored."""
from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-1.2b", family="hybrid",
    num_layers=38, d_model=2048, num_heads=32, num_kv_heads=32, head_dim=128,
    d_ff=8192, vocab_size=32000, rope_theta=10_000.0,
    ssm_state_dim=64, ssm_head_dim=64, ssm_expand=2, ssm_chunk=256,
    ssm_conv_width=4, hybrid_attn_every=6, adapter_rank=128,
    shared_attn_adapters=True, activation="gelu_exact", norm_eps=1e-5,
    tie_embeddings=True, max_seq_len=4096,
)
