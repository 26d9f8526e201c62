"""granite-4.0-h-small [hybrid]: Mamba-2 + MoE, NoPE GQA every tenth layer.

40L (36 Mamba-2, attention at layers 5, 15, 25, 35), d_model=4096,
Mamba-2 128 heads x 64, state 128, 1 group, conv 4 with bias, chunk 256;
attention 32H (GQA kv=8) x 128, no positions; every layer: 72 experts of
768 top-10 plus a shared SwiGLU of 1536; vocab=100352, tied; multipliers
embedding 12, residual 0.22, attention 1/128, logits 1/16.  32.2 B
parameters, 9 B active.
[hf:ibm-granite/granite-4.0-h-small config.json; transformers
GraniteMoeHybrid]
"""
from repro.models.config import ModelConfig, MoEConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    arch_id="granite4_h_small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=768,
    vocab=100352,
    rope_style="none",
    tie_embeddings=True,
    moe=MoEConfig(n_experts=72, top_k=10, expert_d_ff=768),
    ssm=SSMConfig(state_dim=128, version=2, conv_dim=4, expand=2, headdim=64,
                  n_groups=1, chunk=256, conv_bias=True),
    layer_types=_PERIOD * 4,
    shared_d_ff=1536,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
)
