"""granite-4.0-h-small [hybrid] — Mamba-2 / NoPE-GQA layers, each followed
by a mixture of 72 experts (top 10) plus one shared expert.

[hf:ibm-granite/granite-4.0-h-small config.json] 40L in periods of ten
(attention at index 5), d_model=4096, 32H (GQA kv=8), Mamba-2 128 heads of
64, state 128, chunk 256, conv 4 with bias; expert width 768
(``intermediate_size``), shared expert 1536, vocab=100352 tied. Granite's
scalings: embedding x12, every residual branch x0.22, attention scale
1/128, logits / 16; RMSNorm eps 1e-5.
"""
from repro.configs.base import ArchConfig, GLOBAL, SSD, register

GRANITE_4_0_H_SMALL = register(ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,
    vocab=100352,
    period=(SSD,) * 5 + (GLOBAL,) + (SSD,) * 4,
    use_rope=False,
    attn_scale=0.0078125,
    act="silu",
    n_experts=72,
    top_k=10,
    d_ff_expert=768,
    d_ff_shared=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_groups=1,
    conv_width=4,
    ssm_conv_bias=True,
    ssd_mlp=True,
    emb_mult=12.0,
    residual_mult=0.22,
    logits_div=16.0,
    norm_eps=1e-5,
    source="hf:ibm-granite/granite-4.0-h-small config.json",
))
