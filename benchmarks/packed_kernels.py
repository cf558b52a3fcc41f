"""Packed-kernel microbenchmark (DESIGN.md §11): packed GEMV vs
fp32 dense GEMV across H x B x {binary, ternary}, the analytic weight-bytes
ratio those shapes move, and launches-per-tick for the paper-LSTM decode tick.

Backend-honest per the dispatch policy (kernels/dispatch.py): on CPU the
packed number times the jit-compiled XLA lowering of `accumulate_gemv`
(the mul-free select/add loop of the fused decode tick — NEVER
interpret-mode Pallas, which would be thousands of times slower than the
serving path actually is); on the TPU it times the compiled `packed_gemv`
launch.  The `path` field records which one was measured.  The bytes ratio
is analytic (16x ternary, 32x binary — codes only, no scale on the RNN
path) and asserted >= 12x, the paper's memory-bandwidth claim.

Launches-per-tick is counted the way the engine counts tick_traces: trace
one whole decode tick, diff the dispatch launch counter — 1 for the fused
packed tick, 0 for the CPU dense-tables fallback.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import write
from repro.core import bnlstm as BL
from repro.core.qtensor import QTensor
from repro.core.quantize import BINARY_GROUP, TERNARY_GROUP, QuantSpec
from repro.kernels import dispatch
from repro.kernels.packed_matmul import accumulate_gemv, packed_gemv


def _time_us(fn, *args, iters: int = 20) -> float:
    """Median wall micro-seconds of fn(*args) after a compile+warm pass."""
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def _gemv_rows(quick: bool):
    """x (B, H) @ W (H, 4H) — the decode-tick gate-matrix shape."""
    hs = (256,) if quick else (256, 512, 1024)
    bs = (1,) if quick else (1, 4, 8)
    on_cpu = not dispatch.use_pallas(None)
    rows = []
    for h in hs:
        for b in bs:
            k, n = h, 4 * h
            key = jax.random.PRNGKey(h + b)
            w = jax.random.normal(key, (k, n), jnp.float32) * 0.02
            x = jax.random.normal(jax.random.fold_in(key, 1), (b, k),
                                  jnp.float32)
            fp = jax.jit(lambda a, m: a @ m)
            t_fp = _time_us(fp, x, w)
            for mode in ("ternary", "binary"):
                qt = QTensor.from_master(w, mode)
                if on_cpu:
                    path = "xla_accumulate"  # honest: compiled, not interpret
                    pk = jax.jit(functools.partial(accumulate_gemv, mode=mode))
                    t_packed = _time_us(pk, x, qt.codes)
                else:
                    path = "pallas_gemv"
                    pk = jax.jit(functools.partial(packed_gemv, k=k, mode=mode))
                    t_packed = _time_us(pk, x, qt.codes)
                group = TERNARY_GROUP if mode == "ternary" else BINARY_GROUP
                fp_bytes = k * n * 4
                packed_bytes = (k // group) * n * 4
                ratio = fp_bytes / packed_bytes
                assert ratio >= 12, (
                    f"weight-bytes ratio {ratio:.1f}x < the paper's 12x claim")
                rows.append({
                    "bench": "gemv", "mode": mode, "H": h, "B": b,
                    "path": path,
                    "t_packed_us": round(t_packed, 1),
                    "t_fp_us": round(t_fp, 1),
                    "packed_vs_fp": round(t_fp / t_packed, 3),
                    "weight_bytes_fp": fp_bytes,
                    "weight_bytes_packed": packed_bytes,
                    "bytes_ratio": round(ratio, 1),
                })
    return rows


def _tick_rows(quick: bool):
    """Launches traced per whole decode tick, exactly as engine.tick counts
    them: 1 fused packed launch, 0 on the dense CPU fallback."""
    cfg = BL.RNNConfig(vocab=64, d_hidden=128 if quick else 256, n_layers=2,
                       cell="lstm", quant=QuantSpec(mode="ternary",
                                                    norm="batch"))
    var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
    qvar = {"params": BL.export_packed_rnn(var["params"], cfg),
            "state": var["state"]}
    st = BL.rnn_state_init(cfg, 4, per_slot=True)
    tok = jnp.zeros((4,), jnp.int32)
    live = jnp.ones((4,), bool)
    rows = []
    for name, dense in (("packed_whole_tick", False), ("dense_fallback", True)):
        tb = BL.rnn_decode_tables(qvar, cfg, dense=dense)
        n = dispatch.traced_launches(
            lambda t, s: BL.rnn_decode_step(
                qvar, t, cfg, s, tables=tb, live=live,
                interpret=True if not dense else None), tok, st)
        want = 0 if dense else 1
        assert n == want, f"{name}: traced {n} launches per tick, want {want}"
        rows.append({"bench": "tick", "tables": name, "cell": cfg.cell,
                     "layers": cfg.n_layers, "H": cfg.d_hidden,
                     "launches_per_tick": n})
    return rows


def packed_kernels(quick: bool = False):
    rows = _gemv_rows(quick) + _tick_rows(quick)
    write("packed_kernels", rows,
          meta={"backend": dispatch.backend(), "quick": quick,
                "note": "CPU rows time compiled XLA accumulate_gemv, "
                        "never interpret-mode Pallas"})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    for r in packed_kernels(quick=args.quick):
        print(",".join(f"{k}={v}" for k, v in r.items()))
