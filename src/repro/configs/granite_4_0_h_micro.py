"""granite-4.0-h-micro [mamba2_hybrid] — 40 layers at hidden 2048: Mamba-2
mixers (64 heads of 64, d_state 128, one group, conv 4, expand 2, SSD chunk
256) with one NoPE GQA attention layer (32 query, 8 KV heads of 64) at
indices 5, 15, 25 and 35; a SwiGLU MLP of 8192 in every layer; tied
embeddings over a 100,352-token vocabulary; embedding x 12, residual
branches x 0.22, logits / 8, attention scores x 1/64.
[hf:ibm-granite/granite-4.0-h-micro/blob/main/config.json]"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro", family="mamba2_hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab_size=100352,
    ssm_d_state=128, ssm_expand=2, ssm_d_conv=4, ssm_chunk=256,
    ssm_n_heads=64, ssm_n_groups=1,
    attn_layers=(5, 15, 25, 35),
    nope=True, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=0.015625,
    norm_eps=1e-5, tie_embeddings=True,
)
