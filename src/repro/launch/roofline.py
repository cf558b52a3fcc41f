"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms (per chip, seconds) for TPU v5e:

  compute    = HLO_FLOPs / peak_FLOPs          peak = 197 TFLOP/s bf16
  memory     = HLO_bytes / HBM_bw              HBM  = 819 GB/s
  collective = wire_bytes / link_bw            ICI  = ~50 GB/s/link

`cost_analysis()` already reports per-device FLOPs/bytes for the partitioned
module.  Collective wire bytes are NOT in cost_analysis: we parse the
compiled HLO text, take each collective op's per-device result bytes and
apply the ring-algorithm wire factor for its replica-group size g:

  all-reduce     2 * S * (g-1)/g     all-gather      S * (g-1)/g   (S = result)
  reduce-scatter S_in * (g-1)/g      all-to-all      S * (g-1)/g
  collective-permute  S

MODEL_FLOPS uses the 6ND (train) / 2ND (inference) convention with N =
active parameters; the ratio MODEL_FLOPS / HLO_FLOPs exposes remat/dispatch
overhead (a healthy train step with full remat sits near 0.75 = 6/8th... i.e.
1/ratio counts the extra recompute; MoE capacity slack and attention flops
push it further down).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12       # bf16 per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_wire_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-chip wire bytes by collective kind, ring-algorithm accounting."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        size = _shape_bytes(dtype, dims)
        g = 1
        gm = _GROUP_RE.search(line)
        if gm:
            g = int(gm.group(2))
        if g <= 1:
            continue
        frac = (g - 1) / g
        if op == "all-reduce":
            wire = 2.0 * size * frac
        elif op == "collective-permute":
            wire = float(size)
        else:  # all-gather / reduce-scatter / all-to-all
            wire = size * frac
        out[op] = out.get(op, 0.0) + wire
    return out


@dataclasses.dataclass
class Roofline:
    flops: float              # per chip
    hbm_bytes: float          # per chip — XLA 'bytes accessed' (upper bound:
                              # fusion-blind, counts every intermediate)
    wire_bytes: float         # per chip
    collectives: Dict[str, float]
    model_flops: float        # per chip (6ND or 2ND / n_chips)
    hbm_bytes_model: float = 0.0  # analytic HBM traffic (see analytic_hbm_bytes)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory_xla(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_memory(self) -> float:
        """Analytic model when available (the XLA metric has no fusion on
        the CPU pipeline and overstates TPU HBM traffic several-fold)."""
        return (self.hbm_bytes_model or self.hbm_bytes) / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved IF the step runs at the
        bound: (model_flops / peak) / bound_time — the §Perf score basis."""
        if self.bound_time == 0:
            return 0.0
        return (self.model_flops / PEAK_FLOPS) / self.bound_time

    def to_json(self) -> dict:
        return {
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "hbm_bytes_model_per_chip": self.hbm_bytes_model,
            "wire_bytes_per_chip": self.wire_bytes,
            "collectives": self.collectives,
            "model_flops_per_chip": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_memory_xla_s": self.t_memory_xla,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flop_ratio": self.useful_flop_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


# ---------------------------------------------------------------------------
# analytic parameter / FLOP counts per architecture
# ---------------------------------------------------------------------------


def param_counts(cfg) -> tuple[float, float]:
    """(total, active) parameter counts from the config (quantizable matmul
    weights + embeddings; norms/bias omitted — O(d) noise)."""
    d, ff, V = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    hd = cfg.hd
    attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv * hd + cfg.n_heads * hd * d
    if cfg.family == "ssm":  # rwkv6: 5 square tm + channel mix
        per_layer = 5 * d * d + (2 * d * ff + d * d)
        total = cfg.n_layers * per_layer
        active = total
    elif cfg.family == "hybrid":
        di = cfg.d_inner
        GN = cfg.ssm_groups * cfg.ssm_state
        H = cfg.ssm_heads
        mamba = d * (2 * di + 2 * GN + H) + di * d
        # a shared block reads the 2d concat; each call owns its adapter
        # and its d x d linear
        shared = 2 * attn - cfg.n_heads * hd * d + 3 * d * ff
        own = d * d + cfg.adapter_rank * (d + 2 * ff)
        calls = len(cfg.hybrid_ids)
        total = cfg.n_layers * mamba + cfg.n_shared * shared + calls * own
        active = cfg.n_layers * mamba + calls * (shared + own)
    else:
        if cfg.n_experts > 0:
            moe = cfg.n_experts * 3 * d * ff
            act_moe = cfg.topk * 3 * d * ff
            total_layer = attn + moe
            active_layer = attn + act_moe
        elif cfg.mlp == "gelu":
            total_layer = active_layer = attn + 2 * d * ff
        else:
            total_layer = active_layer = attn + 3 * d * ff
        n_dec = cfg.n_layers
        total = n_dec * total_layer
        active = n_dec * active_layer
        if cfg.family == "audio":
            enc_layer = attn + 2 * d * ff
            total += cfg.n_enc_layers * enc_layer
            active += cfg.n_enc_layers * enc_layer
            total += n_dec * (attn + 2 * d * ff) - n_dec * 0  # cross attn per dec layer
            active += n_dec * attn  # xattn
        if cfg.family == "vlm":
            pass  # cross layers already counted via pattern share
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    return total + emb, active + emb


def analytic_hbm_bytes(cfg, shape, n_chips: int, *, weight_bits: float = 16,
                       act_bytes: int = 2) -> float:
    """Per-chip HBM traffic model (documented in EXPERIMENTS.md §Roofline).

    train  = 3 weight streams (fwd + bwd + remat recompute) of the ACTIVE
             bf16 compute weights, + optimizer sweep over the fp32 master/
             m/v shards (7 fp32 passes of TOTAL params, FSDP-sharded), +
             activation checkpoints (layer boundaries, write+read), + KV
             materialization (write+read per layer).
    prefill = 1 weight stream + KV write + causal KV re-reads (chunked:
             each of S/chunk chunks reads ~half the KV written so far).
    decode  = 1 weight-shard stream per token + full KV-cache shard read.

    `weight_bits` models the paper's packed-weight serving path (2 for
    ternary, 1 for binary, 16 for bf16) — the decode weight stream shrinks
    by 16x/32x, which is the TPU translation of the paper's 12x memory-
    bandwidth claim.
    """
    total, active = param_counts(cfg)
    wbytes = active * weight_bits / 8.0
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    n_layers_eff = cfg.n_layers + (cfg.n_enc_layers or 0)

    if cfg.family == "audio":
        from repro.configs.shapes import whisper_dec_len
        dec = whisper_dec_len(S)
        tokens, kv_tokens = B * dec, B * S
    else:
        tokens, kv_tokens = B * S, B * S

    kv_layer_bytes = 2 * cfg.n_kv * cfg.hd * act_bytes  # per token per layer

    if shape.kind == "train":
        w_stream = 3.0 * wbytes
        opt = 7.0 * total * 4.0
        act_ckpt = 2.0 * n_layers_eff * tokens * d * act_bytes
        kv = 2.0 * n_layers_eff * kv_tokens * kv_layer_bytes
        return (w_stream + opt + act_ckpt + kv) / n_chips
    if shape.kind == "prefill":
        n_chunks = max(S // max(cfg.attn_chunk, 1), 1)
        kv_write = n_layers_eff * kv_tokens * kv_layer_bytes
        kv_read = kv_write * n_chunks / 2.0
        act = n_layers_eff * tokens * d * act_bytes
        return (wbytes + kv_write + kv_read + act) / n_chips
    # decode: one token; window layers cap their cache reads
    kv_read = 0.0
    from repro.models.transformer import expand_pattern
    pat, rep, tail = expand_pattern(cfg)
    kinds = list(pat) * rep + list(tail)
    if cfg.family == "hybrid":  # one K/V cache per shared-block call
        kinds += ["full"] * len(cfg.hybrid_ids)
    for k in kinds:
        if k in ("mamba", "rwkv"):
            if cfg.family == "hybrid":
                kv_read += B * cfg.d_inner * cfg.ssm_state * 4
            else:
                H = cfg.d_model // cfg.hd
                kv_read += B * H * cfg.hd * cfg.hd * 4
        elif k == "cross":
            kv_read += B * (cfg.n_img_tokens or S) * kv_layer_bytes
        else:
            ctx = min(cfg.window, S) if (k == "local" or cfg.swa_all) and \
                cfg.window else S
            if cfg.family == "audio":
                ctx = min(448, S)
                kv_read += B * S * kv_layer_bytes  # cross-KV over enc frames
            kv_read += B * ctx * kv_layer_bytes
    return (wbytes + kv_read) / n_chips


def model_flops(cfg, shape, n_chips: int) -> float:
    """Per-chip MODEL_FLOPS: 6·N_active·D train, 2·N_active·D inference."""
    _, active = param_counts(cfg)
    if shape.kind == "train":
        if cfg.family == "audio":
            from repro.configs.shapes import whisper_dec_len
            D = shape.global_batch * whisper_dec_len(shape.seq_len)
        else:
            D = shape.global_batch * shape.seq_len
        return 6.0 * active * D / n_chips
    if shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        return 2.0 * active * D / n_chips
    # decode: one token per sequence
    return 2.0 * active * shape.global_batch / n_chips


def build(cell: dict, cfg, shape, n_chips: int,
          weight_bits: float = 16) -> Roofline:
    colls = cell.get("collectives", {})
    return Roofline(
        flops=cell.get("flops", 0.0),
        hbm_bytes=cell.get("bytes_accessed", 0.0),
        wire_bytes=sum(colls.values()),
        collectives=colls,
        model_flops=model_flops(cfg, shape, n_chips),
        hbm_bytes_model=analytic_hbm_bytes(cfg, shape, n_chips,
                                           weight_bits=weight_bits),
    )
