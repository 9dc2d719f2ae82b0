"""Granite-4.0-H-Micro — hybrid Mamba-2 / GQA decoder
[hf:ibm-granite/granite-4.0-h-micro, model_type granitemoehybrid].

[hybrid] 40L d_model=2048: GQA attention (32 query, 8 KV heads of 64,
no position embedding) at layers 5, 15, 25 and 35; Mamba-2 mixers (64
heads of 64, d_state 128, 1 group, conv 4 with bias, expand 2) in the
other 36; a SwiGLU MLP of 8192 in every layer; vocab 100,352, tied
embeddings, RMSNorm eps 1e-5.  Granite's scalings: embeddings x 12,
attention scores x 1/64, residual branches x 0.22, logits / 8.
"""
from repro.configs.base import ArchConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    tie_embeddings=True,
    rope=False,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_groups=1,
    ssm_conv=4,
    ssm_chunk=256,
    layer_types=_PERIOD * 4,
    norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    block_type="hybrid",
    source="hf:ibm-granite/granite-4.0-h-micro",
)
