"""LFM2-8B-A1B [hf:LiquidAI/LFM2-8B-A1B config.json] — gated short
convolutions and grouped-query attention, sparse experts.

24L d_model=2048, vocab 65536. `layer_types`: 18 gated short-conv
layers (kernel L=3, no bias) and 6 full-attention layers (32 query
heads over 8 KV heads, head_dim 64, per-head q/k RMSNorm, rope theta
1e6). Layers 0-1 have a dense SwiGLU of width 7168; every later layer
has 32 experts of width 1792, top-4 by sigmoid scores plus a constant
expert bias that only chooses, weights normalised over the chosen four
(+1e-6) and scaled by 1.0; no shared experts, no auxiliary loss.
RMSNorm eps 1e-5 everywhere; tied embeddings.
"""
from repro.configs.base import ModelConfig, MoESpec, ATTN, CONV, register

LAYER_TYPES = (CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV, CONV, CONV,
               ATTN, CONV, CONV, CONV, ATTN, CONV, CONV, CONV, ATTN, CONV,
               CONV, ATTN, CONV, CONV)

CONFIG = register(ModelConfig(
    name="lfm2-8b-a1b", family="hybrid",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
    d_ff=7168, vocab=65536, layer_pattern=LAYER_TYPES, conv_cache=3,
    norm="rmsnorm", norm_eps=1e-5, qk_norm=True, rope_theta=1e6,
    tie_embeddings=True,
    moe=MoESpec(n_experts=32, top_k=4, d_ff=1792, n_shared=0, every=1,
                first_dense=2, aux_loss_coef=0.0, score="sigmoid",
                expert_bias=True, routed_scale=1.0),
    source="hf:LiquidAI/LFM2-8B-A1B",
))
