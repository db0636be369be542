"""IBM Granite 4.0-H Micro — Mamba-2 + GQA hybrid, 36 Mamba-2 mixers and 4
NoPE attention layers (indices 5, 15, 25, 35), each with a SwiGLU FFN.
[hf:ibm-granite/granite-4.0-h-micro; config.json]"""
from repro.configs.base import HybridConfig, ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, d_head=64,
    d_ff=8192, vocab_size=100352,
    ffn_act="swiglu", norm="rmsnorm", attn_kind="full",
    hybrid=HybridConfig(pattern=("mamba",) * 5 + ("attention",)
                        + ("mamba",) * 4),
    ssm=SSMConfig(d_state=128, headdim=64, expand=2, n_groups=1,
                  conv_kernel=4, chunk=256, conv_bias=True),
    embed_multiplier=12.0, attn_multiplier=1 / 64,
    residual_multiplier=0.22, logits_scaling=8.0, norm_eps=1e-5,
    pos_embedding="nope",
    tie_embeddings=True,
    source="hf:ibm-granite/granite-4.0-h-micro",
)
