"""zamba2-7b [hybrid] — Zyphra/Zamba2-7B-Instruct at its published widths
(huggingface.co/Zyphra/Zamba2-7B-Instruct, config.json; the layer equations
of transformers' `modeling_zamba2.py`).

81 Mamba2 layers at d_model 3,584: 112 SSD heads of 64, state 64, two B/C
groups, conv 4, expand 2 (d_inner 7,168, conv width 7,424), a group-wise
gated RMSNorm and time steps floored at 0.001.  Two shared attention+MLP
blocks are called in turn (ABAB...) before the 13 mamba layers of
`hybrid_ids`.  A call reads concat(hidden, the token embeddings), 7,168
wide: RMSNorm over the concat, 32 heads of 224 with RoPE (theta 10,000)
and scale (224/2)^-0.5, RMSNorm over the 3,584-wide attention output (no
residual inside the block), a gated-GELU MLP 14,336 wide whose gate_up
gets the call's own rank-128 adapter, then the call's own 3,584 x 3,584
linear.  Its output is added to the next mamba layer's input only, not to
the residual stream.  Vocabulary 32,000, tied embeddings, RMSNorm epsilon
1e-5.

Departures from the published model: the matmul weights are ternary
(the paper's method, packed 16 per uint32) with a per-output-channel
scale where the checkpoint holds bfloat16; served prompts are split into
the engine's prefill chunks, inside which the SSD block decomposition
runs (the recurrence, and so the model, is the same).  Nothing is cut."""
from repro.configs.base import ModelConfig
from repro.core.quantize import QuantSpec


def config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b",
        family="hybrid",
        n_layers=81,
        d_model=3584,
        n_heads=32,
        n_kv=32,
        head_dim=224,
        d_ff=14336,
        vocab=32000,
        rope_theta=10000.0,
        tie_embeddings=True,
        ssm_state=64,
        ssm_expand=2,
        ssm_headdim=64,
        ssm_conv=4,
        ssm_groups=2,
        hybrid_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
        n_shared=2,
        adapter_rank=128,
        ssm_chunk=256,
        quant=QuantSpec(mode="ternary", norm="channel"),
    )
