"""Operations and bytes of the work a BN-LSTM call has to do, from the
model's shapes alone, so that every implementation is held to the same
count.  Operations are multiply-accumulate equivalents, 2 per weight per
row (a select-and-add against a ternary code counts as one such pair).

Least time of a call = max(ops / bf16 peak, bytes / HBM bandwidth)."""
from __future__ import annotations

N_GATES = 4
CODE_BITS = {"ternary": 2, "binary": 1, "none": 32}
F32 = 4


def recurrent_matrices(cfg: dict):
    """(K, N) of every matrix a decode step multiplies: the h-side of each
    layer and the x-side of layers >= 1 (layer 0's input is a row gather)."""
    H, g = cfg["d_hidden"], N_GATES
    mats = [(H, g * H)] * cfg["n_layers"]
    mats += [(H, g * H)] * (cfg["n_layers"] - 1)
    return mats


def model_flops_per_token(cfg: dict) -> float:
    """The recurrent matrices plus the softmax head, one token."""
    macs = sum(k * n for k, n in recurrent_matrices(cfg))
    return 2.0 * (macs + cfg["d_hidden"] * cfg["vocab"])


def recurrent_flops_per_token(cfg: dict) -> float:
    """One prompt token of prefill: the head runs once per prompt, not per
    token, and is left out."""
    return 2.0 * sum(k * n for k, n in recurrent_matrices(cfg))


def weight_bytes(k: int, n: int, quant: str) -> float:
    return k * n * CODE_BITS[quant] / 8.0


def decode_tick_work(cfg: dict, rows: int) -> tuple:
    """(ops, bytes) of the fused decode tick over `rows` slots: every
    recurrent matrix once as codes, the h and c of every layer read and
    written, and layer 0's gathered input pre-activation read.  The head is
    left out (it belongs to the model step, not to this kernel)."""
    H, L, g = cfg["d_hidden"], cfg["n_layers"], N_GATES
    mats = recurrent_matrices(cfg)
    ops = 2.0 * rows * sum(k * n for k, n in mats)
    wbytes = sum(weight_bytes(k, n, cfg["quant"]) for k, n in mats)
    state = 4 * L * rows * H * F32            # h, c in and out
    act = rows * g * H * F32                  # layer 0 pre-activation
    return ops, wbytes + state + act


def gemv_work(cfg: dict, rows: int) -> tuple:
    """(ops, bytes) of one packed GEMV of prefill: a (rows, H) activation
    against one h-side matrix (H, 4H), its codes read once."""
    k, n = cfg["d_hidden"], N_GATES * cfg["d_hidden"]
    ops = 2.0 * rows * k * n
    return ops, weight_bytes(k, n, cfg["quant"]) + rows * (k + n) * F32


def least_seconds(work: tuple, peak: dict) -> float:
    ops, nbytes = work
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])
