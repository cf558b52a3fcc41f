"""Operations and bytes of the work a Zamba2 decode tick has to do, from
the configuration file's published widths alone (`zamba2_data.dims`), so
that every implementation is held to the same count.  Operations are
multiply-accumulate equivalents, 2 per weight per row (a select-and-add
against a ternary code counts as one such pair).

Least time of a piece of work = max(ops / bf16 peak, bytes / HBM
bandwidth), as in bench/work.py."""
from __future__ import annotations

from bench.models.zamba2_data import dims

CODE_BYTES = 2 / 8        # a ternary weight: 2 bits
F32 = 4


def _per_call(D: dict) -> list:
    d, hd = D["d"], D["hd"]
    return [(2 * d, D["heads"] * hd), (2 * d, D["kv_heads"] * hd),
            (2 * d, D["kv_heads"] * hd), (D["heads"] * hd, d),
            (d, 2 * D["ff"]), (D["ff"], d), (d, D["rank"]),
            (D["rank"], 2 * D["ff"]), (d, d)]


def matrices(cfg: dict) -> list:
    """(K, N) of every packed matrix one token passes through, once per
    use: each mamba layer's in/out projections, and at each of the calls
    its shared block's q, k, v, o, gate_up and down and its own adapter
    pair and linear.  A block's weights are read again at every call."""
    D = dims(cfg)
    mamba = [(D["d"], D["proj"]), (D["di"], D["d"])] * D["layers"]
    return mamba + _per_call(D) * len(D["ids"])


def stored_matrices(cfg: dict) -> list:
    """(K, N) of every packed matrix the model holds: the blocks' six once
    per block, the adapters and linears once per call."""
    D = dims(cfg)
    per_call = _per_call(D)
    return ([(D["d"], D["proj"]), (D["di"], D["d"])] * D["layers"]
            + per_call[:6] * D["blocks"] + per_call[6:] * len(D["ids"]))


def quantized_params(cfg: dict) -> int:
    return sum(k * n for k, n in stored_matrices(cfg))


def code_bytes(cfg: dict) -> float:
    return quantized_params(cfg) * CODE_BYTES


def model_flops_per_token(cfg: dict) -> float:
    """2 per multiply-accumulate of every matrix a token passes through
    (`matrices`: the shared blocks once per call) and of the tied head;
    22.1 GFLOP at the published widths."""
    D = dims(cfg)
    return 2.0 * (sum(k * n for k, n in matrices(cfg)) + D["d"] * D["vocab"])


def gemv_work(k: int, n: int, rows: int) -> tuple:
    """(ops, bytes) of one packed GEMV: a (rows, k) float32 activation
    against (k, n) ternary codes, read once, and its (rows, n) output."""
    return 2.0 * rows * k * n, k * n * CODE_BYTES + rows * (k + n) * F32


def tick_gemv_least_s(cfg: dict, rows: int, peak: dict) -> float:
    """Least time of every packed matrix of a tick at `rows` rows."""
    return sum(least_seconds(gemv_work(k, n, rows), peak)
               for k, n in matrices(cfg))


def state_bytes(cfg: dict, slots: int, context: float) -> float:
    """Bytes of recurrent and attention state a tick over `slots` rows
    must move: every mamba layer's h read and written and its conv tail
    read and written, for every row; every call's keys and values read at
    `context` positions (summed over the live rows) and written once per
    live row is left out as small."""
    D = dims(cfg)
    h = D["layers"] * slots * D["H"] * D["N"] * D["P"] * F32 * 2
    conv = D["layers"] * slots * (D["K"] - 1) * D["conv"] * F32 * 2
    kv = len(D["ids"]) * context * D["kv_heads"] * D["hd"] * F32 * 2
    return h + conv + kv


def least_seconds(work: tuple, peak: dict) -> float:
    ops, nbytes = work
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
