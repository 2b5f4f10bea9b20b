"""granite-4.0-h-small [hybrid]: 40L d_model=4096, 36 Mamba-2 mixers (128
heads of 64, d_state 128, expand 2, conv 4, 1 group, chunk 256) and 4 GQA
attention layers (32 q / 8 kv heads of 128, no positional embedding) at
layers 5, 15, 25, 35; every mixer is followed by a routed FFN of 72
experts of width 768, top-10, plus a shared expert of width 1536.
vocab=100352, tied. Granite's multipliers: embedding 12, residual 0.22,
attention 1/128, logits / 16; RMSNorm eps 1e-5. 32.2B parameters, 8.8B
active. [hf:ibm-granite/granite-4.0-h-small]"""
import dataclasses

from repro.configs.base import HybridConfig, ModelConfig, MoEConfig, SSMConfig

# one 10-layer period: 5 Mamba-2, 1 attention, 4 Mamba-2
PERIOD = ("ssm_moe",) * 5 + ("attn_moe",) + ("ssm_moe",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    num_layers=40, d_model=4096, num_heads=32, kv_heads=8,
    d_ff=768, vocab=100352, head_dim=128, tie_embeddings=True,
    norm="rmsnorm", act="silu", gated_ffn=True,
    position_embedding="nope",
    hybrid=HybridConfig(pattern=PERIOD),
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256, num_groups=1),
    moe=MoEConfig(num_experts=72, top_k=10, capacity_factor=0.0,
                  shared_expert=True, shared_d_ff=1536),
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0, norm_eps=1e-5,
)

SMOKE = dataclasses.replace(
    CONFIG, name="granite-4-smoke", d_model=64, num_heads=4, kv_heads=2,
    head_dim=16, d_ff=32, vocab=256, num_layers=10,
    ssm=SSMConfig(state_dim=16, head_dim=16, expand=2, conv_width=4,
                  chunk=8, num_groups=1),
    moe=MoEConfig(num_experts=8, top_k=3, capacity_factor=0.0,
                  shared_expert=True, shared_d_ff=48),
    attention_multiplier=0.125)
