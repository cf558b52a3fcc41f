"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes and extract the roofline terms (EXPERIMENTS.md
§Dry-run / §Roofline).

The os.environ lines below MUST run before any jax import — jax locks the
device count at first init.  Do not move them.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
  python -m repro.launch.dryrun --all --mesh single --skip-done   # resume
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import (SHAPES, applicable, decode_context,
                                  decode_inputs, prefill_inputs, token_batch)
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import build as build_roofline
from repro.launch.roofline import collective_wire_bytes
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   compute_param_pspec, param_pspec,
                                   param_shardings, serve_param_pspec,
                                   serve_param_shardings, state_shardings)
from repro.models import transformer as T
from repro.runtime import use_mesh
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, train_state_init

jax.config.update("jax_default_prng_impl", "rbg")  # cheap keys for eval_shape


def _params_struct(cfg):
    key = jax.ShapeDtypeStruct((4,), jnp.uint32)
    return jax.eval_shape(lambda k: T.model_init(k, cfg), key)


def _state_struct(cfg, opt_cfg):
    key = jax.ShapeDtypeStruct((4,), jnp.uint32)

    def mk(k):
        params = T.model_init(k, cfg)
        return train_state_init(params, opt_cfg, k)

    return jax.eval_shape(mk, key)


VARIANTS = ("baseline", "packed", "servetp", "dots", "parallel",
            "packed+servetp", "packed+dots", "parallel+dots",
            "parallel+packed+dots")


def apply_variant(cfg, variant: str):
    """Beyond-paper optimization toggles (EXPERIMENTS.md §Perf):
      packed  — FSDP/TP weight gathers move 2-bit/1-bit codes
      servetp — serve cells store weights TP-only + bf16 (no per-token gather)
      dots    — remat policy saves matmul outputs (~8ND -> 6ND train flops)
    """
    parts = set(variant.split("+"))
    if "packed" in parts and cfg.quant.mode in ("binary", "ternary"):
        cfg = cfg.with_quant(dataclasses.replace(cfg.quant, packed_comms=True))
    if "dots" in parts:
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    if "parallel" in parts:
        cfg = dataclasses.replace(cfg, parallel_block=True)
    return cfg, ("servetp" in parts)


def lower_cell(cfg, shape_name: str, multi_pod: bool, serve_tp: bool = False):
    """Returns (lowered, n_chips, meta) for one grid cell."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    opt_cfg = OptConfig(kind="adamw", lr=1e-4)

    serve_cell = shape.kind in ("prefill", "decode")
    rules = serve_param_pspec if (serve_tp and serve_cell) else param_pspec
    p_shard_fn = serve_param_shardings if (serve_tp and serve_cell) \
        else param_shardings

    def params_struct():
        params = _params_struct(cfg)
        if serve_tp and serve_cell:
            # deployment layout: bf16 weights, no fp32 masters on the pod
            params = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                if jnp.issubdtype(s.dtype, jnp.floating) else s, params)
        return params

    with use_mesh(mesh, param_rules=rules, compute_rules=compute_param_pspec):
        if shape.kind == "train":
            state = _state_struct(cfg, opt_cfg)
            batch = token_batch(cfg, shape.global_batch, shape.seq_len)
            in_sh = (state_shardings(state, mesh), batch_shardings(batch, mesh))
            step = make_train_step(cfg, opt_cfg)
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=(in_sh[0], None)).lower(state, batch)
        elif shape.kind == "prefill":
            params = params_struct()
            inputs = prefill_inputs(cfg, shape.global_batch, shape.seq_len)
            ctx, src = decode_context(cfg, shape.seq_len)
            caches = jax.eval_shape(
                lambda: T.init_caches(cfg, shape.global_batch, ctx, src_len=src))
            p_sh = p_shard_fn(params, mesh)
            c_sh = cache_shardings(caches, mesh)
            i_sh = batch_shardings(inputs, mesh)

            def step(params, caches, inputs):
                return T.prefill(params, inputs["tokens"], caches, cfg,
                                 img=inputs.get("img"),
                                 enc_frames=inputs.get("enc_frames"))

            lowered = jax.jit(step, in_shardings=(p_sh, c_sh, i_sh),
                              out_shardings=(None, c_sh)).lower(
                                  params, caches, inputs)
        else:  # decode
            params = params_struct()
            inputs = decode_inputs(cfg, shape.global_batch)
            ctx, src = decode_context(cfg, shape.seq_len)
            caches = jax.eval_shape(
                lambda: T.init_caches(cfg, shape.global_batch, ctx, src_len=src))
            # decode against a FULL cache: pos = context length
            caches = jax.tree.map(lambda x: x, caches)
            p_sh = p_shard_fn(params, mesh)
            c_sh = cache_shardings(caches, mesh)
            i_sh = batch_shardings(inputs, mesh)

            def step(params, caches, inputs):
                return T.decode_step(params, inputs["token"], caches, cfg)

            lowered = jax.jit(step, in_shardings=(p_sh, c_sh, i_sh),
                              out_shardings=(None, c_sh)).lower(
                                  params, caches, inputs)
    return lowered, n_chips, {"mesh": tuple(mesh.shape.values())}


def _measure(cfg, shape_name: str, multi_pod: bool,
             serve_tp: bool = False) -> dict:
    t0 = time.time()
    lowered, n_chips, meta = lower_cell(cfg, shape_name, multi_pod, serve_tp)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    return {
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": collective_wire_bytes(compiled.as_text()),
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        },
    }


def _combine(main: dict, b1: dict, b0: dict, mult: float) -> dict:
    """main + mult * (b1 - b0) on flops/bytes/collectives.

    XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, not x trip
    count; the corrected totals add (R-1) copies of the measured per-repeat
    body delta.  memory_analysis needs no correction (scan reuses buffers)."""
    out = dict(main)
    for k in ("flops", "bytes_accessed"):
        out[k] = main[k] + mult * max(b1[k] - b0[k], 0.0)
    colls = dict(main["collectives"])
    keys = set(b1["collectives"]) | set(b0["collectives"])
    for k in keys:
        d = b1["collectives"].get(k, 0.0) - b0["collectives"].get(k, 0.0)
        if d > 0:
            colls[k] = colls.get(k, 0.0) + mult * d
    out["collectives"] = colls
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    cell = {"arch": arch, "shape": shape_name, "variant": variant,
            "mesh": "multi" if multi_pod else "single"}
    if not ok:
        cell.update(status="skipped", reason=why)
        return cell
    cfg, serve_tp = apply_variant(cfg, variant)
    try:
        main = _measure(cfg, shape_name, multi_pod, serve_tp)
        cell["raw"] = {k: main[k] for k in ("flops", "bytes_accessed",
                                            "collectives")}

        # scan-trip-count correction via two cheap unrolled aux compiles
        from repro.models.transformer import expand_pattern
        pat, rep, tail = expand_pattern(cfg)
        corrected = main
        # (a hybrid's call sites are fixed layer ids: its scan is not
        # corrected)
        if rep > 1 and cfg.family != "hybrid":
            per = len(cfg.block_pattern)
            cfg0 = dataclasses.replace(cfg, n_layers=0, n_enc_layers=0,
                                       unroll=True)
            cfg1 = dataclasses.replace(cfg, n_layers=per, n_enc_layers=0,
                                       unroll=True)
            b0 = _measure(cfg0, shape_name, multi_pod, serve_tp)
            b1 = _measure(cfg1, shape_name, multi_pod, serve_tp)
            corrected = _combine(main, b1, b0, rep - 1)
            if (cfg.family == "audio" and cfg.n_enc_layers > 1
                    and shape.kind != "decode"):
                e1 = _measure(dataclasses.replace(cfg, n_layers=0,
                                                  n_enc_layers=1, unroll=True),
                              shape_name, multi_pod, serve_tp)
                corrected = _combine(corrected, e1, b0, cfg.n_enc_layers - 1)

        cell.update(status="ok", **corrected)
        wb = 16
        if "packed" in variant:
            wb = {"ternary": 2, "binary": 1}.get(cfg.quant.mode, 16)
        rf = build_roofline(cell, cfg, shape, main["n_chips"], weight_bits=wb)
        cell["roofline"] = rf.to_json()
    except Exception as e:  # record failures — they are bugs to fix
        cell.update(status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-2000:])
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="baseline", choices=VARIANTS)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = ARCH_IDS if args.all or not args.arch else (args.arch,)
    shapes = tuple(SHAPES) if args.all or not args.shape else (args.shape,)
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)

    n_ok = n_err = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                suffix = "" if args.variant == "baseline" else f"__{args.variant}"
                path = out / f"{arch}__{shape}__{mesh_kind}{suffix}.json"
                if args.skip_done and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        continue
                cell = run_cell(arch, shape, mesh_kind == "multi",
                                args.variant)
                path.write_text(json.dumps(cell, indent=1))
                st = cell["status"]
                n_ok += st == "ok"
                n_err += st == "error"
                n_skip += st == "skipped"
                msg = ""
                if st == "ok":
                    r = cell["roofline"]
                    msg = (f"dom={r['dominant']} tc={r['t_compute_s']:.3e} "
                           f"tm={r['t_memory_s']:.3e} tx={r['t_collective_s']:.3e} "
                           f"compile={cell['compile_s']}s")
                elif st == "error":
                    msg = cell["error"][:140]
                else:
                    msg = cell["reason"][:80]
                print(f"[{st:7s}] {arch:22s} {shape:12s} {mesh_kind:6s} {msg}",
                      flush=True)
    print(f"done: {n_ok} ok, {n_err} errors, {n_skip} skipped")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
