"""mellum2-12b-a2.5b [moe] — hf:JetBrains/Mellum2-12B-A2.5B-Instruct.
28L d=2304 32H (GQA kv=4, head_dim 128) vocab=98304; layers repeat
sliding, sliding, sliding, full (window 1024; YaRN rope on full layers,
default rope on sliding ones, both θ 500k); every layer sparse: 64 experts
top-8 (softmax, renormalised), SwiGLU experts of width 896, no shared
expert; RMSNorm eps 1e-6, untied head, no attention bias.

The expert layer runs as one chip's expert-parallel share: it routes over
all 64 experts and computes the part its ``moe_held`` experts give (all 64
here; a deployment sets the ids its chip holds)."""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4, head_dim_=128,
    d_ff=896, vocab=98_304, rope_theta=500_000.0,
    norm_eps=1e-6,
    block_pattern=("attn_moe",) * 4, layer_windows=(1024, 1024, 1024, 0),
    rope_yarn=Yarn(factor=16.0, original_max=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.2772588722239782),
    n_experts=64, top_k=8, renorm_topk=True, moe_held=tuple(range(64)),
    moe_aux_weight=0.001, max_seq=131_072,
)
