"""Unified model configuration for every assigned architecture.

One frozen dataclass covers the whole pool; family-specific fields are ignored
by families that don't use them.  `reduced()` produces the small smoke-test
variant of the same family (same code paths, tiny dims).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.quantize import QuantSpec


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 500000.0
    causal: bool = True
    mlp: str = "swiglu"  # swiglu | gelu
    attn_softcap: float = 0.0
    tie_embeddings: bool = False

    # heterogeneous layer stacks: repeating pattern of layer kinds
    # kinds: full | local | global | self | cross | mamba | rwkv
    block_pattern: Tuple[str, ...] = ("full",)
    window: int = 0  # sliding window for 'local' kind / swa_all
    swa_all: bool = False  # mixtral: SWA on every layer

    # moe
    n_experts: int = 0
    topk: int = 0
    capacity_factor: float = 1.25

    # ssm / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    ssm_groups: int = 1  # B/C groups; heads of a group share B and C
    # zamba2 hybrids: the shared attention+MLP blocks (`n_shared` of them,
    # called in turn) run before the mamba layers listed in `hybrid_ids`;
    # each call has its own rank-`adapter_rank` gate_up adapter and its own
    # d x d output projection (models/transformer.py, hybrid path)
    hybrid_ids: Tuple[int, ...] = ()
    n_shared: int = 1
    adapter_rank: int = 0

    # enc-dec (whisper): n_layers = decoder depth
    n_enc_layers: int = 0
    max_target_len: int = 448

    # vlm
    n_img_tokens: int = 0

    # quantization (the paper's technique)
    quant: QuantSpec = QuantSpec(mode="none")

    # numerics / structure
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "full"  # full | dots (save matmul outputs: ~8ND->6ND
    #                             train flops for more checkpoint memory)
    attn_chunk: int = 1024
    ssm_chunk: int = 64
    vocab_pad_to: int = 128
    # replace the over-repeats lax.scan with a python loop.  Used by the
    # dry-run's scan-correction compiles (XLA cost_analysis counts a loop
    # body once, not x trip-count) and available for small-depth runs.
    unroll: bool = False
    # PaLM-style parallel attention+MLP residual: one shared pre-norm, the
    # two row-parallel outputs sum BEFORE the TP all-reduce, halving the
    # per-layer activation collectives (beyond-paper §Perf variant; changes
    # the model function, so it is a training-time architecture choice).
    parallel_block: bool = False

    # capability flags
    sub_quadratic: bool = False  # eligible for long_500k

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab + p - 1) // p * p

    @property
    def d_inner(self) -> int:  # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def with_quant(self, spec: QuantSpec) -> "ModelConfig":
        return dataclasses.replace(self, quant=spec)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        pat = self.block_pattern
        if self.family == "hybrid":
            # six mamba layers, three shared-block calls at irregular gaps
            # (a prefix of 1, then 2, 1, 2), both shared blocks and both
            # B/C groups kept; head_dim = 2 * d_model / n_heads as published
            return dataclasses.replace(
                self, n_layers=6, d_model=64, n_heads=4, n_kv=4, head_dim=32,
                d_ff=128, vocab=256, ssm_state=16, ssm_headdim=16,
                ssm_groups=min(self.ssm_groups, 2),
                n_shared=min(self.n_shared, 2), hybrid_ids=(1, 3, 4),
                adapter_rank=min(self.adapter_rank, 8), attn_chunk=32,
                ssm_chunk=8, dtype="float32")
        n_layers = max(len(pat), 2)
        return dataclasses.replace(
            self,
            n_layers=n_layers,
            d_model=64,
            n_heads=4,
            n_kv=min(self.n_kv, 2) if self.n_kv < self.n_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab=256,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            topk=min(self.topk, 2) if self.topk else 0,
            window=min(self.window, 16) if self.window else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=16,
            n_enc_layers=min(self.n_enc_layers, 2),
            n_img_tokens=min(self.n_img_tokens, 16) if self.n_img_tokens else 0,
            attn_chunk=32,
            ssm_chunk=8,
            dtype="float32",
        )


# `head_dim` note: configs specify d_model and n_heads; where the public model
# card gives an explicit head_dim != d_model/n_heads (gemma3, qwen3) we set it.
