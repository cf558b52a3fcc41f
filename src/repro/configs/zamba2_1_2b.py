"""zamba2-1.2b [hybrid] — 38 Mamba2 layers and ONE shared attention+MLP
block called before every sixth mamba layer [arXiv:2411.15242].  SSM state
64, headdim 64 -> 64 SSD heads, one B/C group.

It runs the Zamba2 hybrid block exactly as `zamba2-7b` does (the shared
block reads concat(hidden, embeddings), its output goes through a per-call
linear into the next mamba layer's input; models/transformer.py).  Assumed,
not read from the published config: the call sites (every sixth layer from
6), no attention adapters, the rank-128 gate_up adapter, head_dim
2 * d_model / n_heads = 128, RoPE at theta 10,000 and the epsilons."""
from repro.configs.base import ModelConfig
from repro.core.quantize import QuantSpec


def config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-1.2b",
        family="hybrid",
        n_layers=38,
        d_model=2048,
        n_heads=32,
        n_kv=32,
        head_dim=128,
        d_ff=8192,
        vocab=32000,
        rope_theta=10000.0,
        tie_embeddings=True,
        ssm_state=64,
        ssm_expand=2,
        ssm_headdim=64,
        ssm_groups=1,
        hybrid_ids=(6, 12, 18, 24, 30, 36),
        n_shared=1,
        adapter_rank=128,
        ssm_chunk=64,
        sub_quadratic=True,
        quant=QuantSpec(mode="ternary", norm="channel"),
    )
