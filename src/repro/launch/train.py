"""Training launcher: data -> prefetch -> pjit step -> checkpoint/restart.

Runs at every scale with the same code path:
  * CPU/dev box:  python -m repro.launch.train --arch qwen3-0.6b --reduced \
                      --steps 50
  * pod/fleet:    the same command under the TPU runtime with --mesh-model 16
                  (the launcher builds the largest feasible mesh from
                  jax.devices() via train/elastic.py, so losing hosts between
                  restarts re-shapes automatically — elastic scaling).

The paper's own BN-LSTM trains through the same launcher:

  python -m repro.launch.train --arch rnn-paper --reduced --steps 300

routes RNN_ARCH_IDS through get_rnn_config -> make_rnn_train_step (bn_state
threaded through TrainState), evaluates validation BPC on a held-out split,
and drives the paper's /4-on-plateau LR schedule from the journaled eval
curve — the journal is replayed on restart so a resumed run derives the
exact lr_scale the interrupted run was using.

--pipeline closes the whole loop in one command (DESIGN.md §13): train with
a real mid-run SIGTERM + restart, prove the resumed run is bit-identical to
an uninterrupted one, export the trained masters to packed ternary QTensors
with frozen BN statistics, serve them through ServeEngine with byte parity
against the sequential oracle, and measure the trained masters' speculative-
decoding acceptance rate.  Results land in results/benchmarks/train_rnn.json.

Fault-tolerance contract: SIGTERM => checkpoint + exit 43 (launcher restarts
with --resume auto); checkpoints are atomic; the data pipeline is step-
indexed so restart is sample-exact.  Checkpoint index == number of COMPLETED
steps == the next step to run (both the periodic and the preemption path
save the post-update state under step+1).  A per-step EWMA straggler monitor
logs slow hosts (single-host here; the record() feed is a collective on
fleets).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (ARCH_IDS, RNN_ARCH_IDS, get_config, get_rnn_config,
                           rnn_paper)
from repro.core import bnlstm as BL
from repro.core.quantize import QuantSpec
from repro.data.loader import Prefetcher
from repro.data.synth import markov_bytes, token_stream
from repro.data.text import ByteCorpus
from repro.launch.sharding import (batch_shardings, param_pspec,
                                   state_shardings)
from repro.launch.mesh import make_host_mesh
from repro.runtime import device_banner, enable_compile_cache, use_mesh
from repro.train import checkpoint as CK
from repro.train.elastic import best_mesh_shape, make_mesh_from_plan
from repro.train.fault_tolerance import (RESTART_EXIT_CODE, PreemptionHandler,
                                         StepTimer, StragglerMonitor)
from repro.train.optimizer import OptConfig, PlateauLR
from repro.train.train_step import (make_rnn_eval, make_rnn_train_step,
                                    make_train_step, train_state_init)
from repro.models import transformer as T

RESULTS = Path(__file__).resolve().parents[3] / "results" / "benchmarks"


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + RNN_ARCH_IDS,
                    default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family")
    ap.add_argument("--quant", default=None,
                    choices=("none", "binary", "ternary"),
                    help="override the config's weight quantization")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--opt", default=None, choices=("adamw", "sgd"),
                    help="optimizer (default: adamw)")
    ap.add_argument("--momentum", type=float, default=0.0,
                    help="SGD momentum (paper word-PTB uses plain SGD)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' | path to a text file/dir")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="none", choices=("none", "auto"))
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    # RNN (rnn-paper) training
    ap.add_argument("--eval-every", type=int, default=50,
                    help="validation-BPC cadence; drives the plateau LR")
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--plateau-factor", type=float, default=0.25,
                    help="LR multiplier on val rise (paper: /4); 0 disables")
    # the one-command train->restart->export->serve proof
    ap.add_argument("--pipeline", action="store_true",
                    help="train with a real SIGTERM restart, verify the "
                         "resume bit-exactly, export packed weights, serve "
                         "through ServeEngine; writes "
                         "results/benchmarks/train_rnn.json")
    ap.add_argument("--quick", action="store_true",
                    help="CI scale for --pipeline (fewer steps)")
    return ap


# ---------------------------------------------------------------------------
# the paper's BN-LSTM / BN-GRU
# ---------------------------------------------------------------------------


def _rnn_corpus(args) -> ByteCorpus:
    """Byte corpus with train/valid/test splits.  'synthetic' generates the
    order-2 Markov stand-in matched to char-PTB's ~50-symbol vocab (offline
    container; see benchmarks/common.py for the caveats on absolute BPC)."""
    if args.data == "synthetic":
        data = np.asarray(markov_bytes(120_000, vocab=50, seed=args.seed))
        return ByteCorpus.from_bytes(bytes(bytearray(data % 256)))
    p = Path(args.data)
    return ByteCorpus.from_dir(p) if p.is_dir() else ByteCorpus.from_files([p])


def _rnn_cfg(args, corpus: ByteCorpus) -> BL.RNNConfig:
    cfg = get_rnn_config(args.arch)
    if args.reduced:
        cfg = rnn_paper.reduced(cfg)
    if args.quant is not None:
        spec = (QuantSpec(mode=args.quant, norm="batch")
                if args.quant != "none" else QuantSpec(mode="none"))
        cfg = dataclasses.replace(cfg, quant=spec)
    # the corpus' dense byte vocab is the model's vocab (it can be smaller
    # than the config's nominal size when symbols are unused)
    return dataclasses.replace(cfg, vocab=corpus.vocab)


def _read_curve(path: Path) -> list:
    if not path.exists():
        return []
    return [json.loads(l) for l in path.read_text().splitlines() if l.strip()]


def run_rnn(args):
    """Train the paper's BN-LSTM char-LM; returns the final TrainState."""
    corpus = _rnn_corpus(args)
    cfg = _rnn_cfg(args, corpus)
    print(f"rnn-paper: cell={cfg.cell} hidden={cfg.d_hidden} "
          f"vocab={cfg.vocab} quant={cfg.quant.mode} "
          f"corpus={len(corpus.data)} tokens", flush=True)

    mesh = None
    if args.compress_grads:
        # pure data parallelism over whatever devices exist (a 1-device mesh
        # still exercises the shard_map compressed path end-to-end)
        mesh = make_host_mesh()

    opt_cfg = OptConfig(kind=args.opt or "adamw", lr=args.lr,
                        momentum=args.momentum, clip_norm=1.0,
                        warmup_steps=args.warmup)
    var = BL.rnn_lm_init(jax.random.PRNGKey(args.seed), cfg)
    state = train_state_init(var["params"], opt_cfg,
                             jax.random.PRNGKey(args.seed + 1),
                             bn_state=var["state"],
                             compress=args.compress_grads)
    jstep = jax.jit(make_rnn_train_step(cfg, opt_cfg, mesh=mesh,
                                        compress_grads=args.compress_grads))
    jeval = jax.jit(make_rnn_eval(cfg))

    def val_bpc(st) -> float:
        bpcs = [float(jeval(st, corpus.batch("valid", i, args.batch,
                                             args.seq))["bpc"])
                for i in range(args.eval_batches)]
        return float(np.mean(bpcs))

    plateau = PlateauLR(factor=args.plateau_factor or 0.25)
    start_step = 0
    ckpt = None
    curve_path = None
    if args.ckpt_dir:
        ckpt = CK.AsyncCheckpointer(args.ckpt_dir)
        Path(args.ckpt_dir).mkdir(parents=True, exist_ok=True)
        curve_path = Path(args.ckpt_dir) / "val_curve.jsonl"
        if args.resume == "auto" and CK.latest_step(args.ckpt_dir) is not None:
            start_step = CK.latest_step(args.ckpt_dir)
            state = CK.restore(state, args.ckpt_dir, start_step)
            # rebuild the plateau schedule from the journaled eval curve:
            # entries past the checkpoint (eval ran, save didn't) are
            # truncated so the resumed run re-derives them identically
            curve = [e for e in _read_curve(curve_path)
                     if e["step"] <= start_step]
            curve_path.write_text(
                "".join(json.dumps(e) + "\n" for e in curve))
            scale0 = plateau.replay([e["val_bpc"] for e in curve])
            print(f"resumed from step {start_step} "
                  f"(lr_scale {scale0} from {len(curve)} journaled evals)",
                  flush=True)

    handler = PreemptionHandler()
    monitor = StragglerMonitor(n_hosts=jax.process_count())
    prefetch = Prefetcher(
        lambda s: corpus.batch("train", s, args.batch, args.seq),
        start_step, mesh=mesh)

    scale = plateau.scale
    t_start = time.time()
    with use_mesh(mesh):
        for step, batch in prefetch:
            if step >= args.steps:
                break
            with StepTimer() as tm:
                state, metrics = jstep(state, batch,
                                       jnp.asarray(scale, jnp.float32))
                jax.block_until_ready(metrics["loss"])
            monitor.record(jax.process_index(), tm.dt)
            if not np.isfinite(float(metrics["loss"])):
                raise SystemExit(f"step {step}: loss "
                                 f"{float(metrics['loss'])} is not finite")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:6d} loss {float(metrics['loss']):.4f} "
                      f"bpc {float(metrics['bpc']):.3f} "
                      f"lr {float(metrics.get('lr', 0)):.2e} "
                      f"{tm.dt*1e3:.0f} ms", flush=True)
            done = step + 1
            if args.plateau_factor and (done % args.eval_every == 0
                                        or done == args.steps):
                v = val_bpc(state)
                scale = plateau.update(v)
                print(f"eval  step {done:6d} val_bpc {v:.4f} "
                      f"lr_scale {scale}", flush=True)
                if curve_path is not None:
                    with curve_path.open("a") as f:
                        f.write(json.dumps({"step": done, "val_bpc": v})
                                + "\n")
            if ckpt and done % args.ckpt_every == 0 and done < args.steps:
                ckpt.save_async(state, done)
            if handler.preempted:
                print("preempted: checkpointing and exiting 43", flush=True)
                if ckpt:
                    ckpt.wait()
                    CK.save(state, args.ckpt_dir, done)
                prefetch.close()
                sys.exit(RESTART_EXIT_CODE)

    prefetch.close()
    if ckpt:
        ckpt.wait()
        CK.save(state, args.ckpt_dir, args.steps)
    dt = time.time() - t_start
    print(f"done: {args.steps - start_step} steps in {dt:.1f}s "
          f"({(args.steps - start_step) / max(dt, 1e-9):.2f} steps/s)")
    return state


# ---------------------------------------------------------------------------
# the one-command pipeline: train -> SIGTERM/restart -> export -> serve
# ---------------------------------------------------------------------------


def _child_cmd(args, ckpt_dir: Path) -> list:
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", args.arch, "--steps", str(args.steps),
           "--batch", str(args.batch), "--seq", str(args.seq),
           "--lr", str(args.lr), "--warmup", str(args.warmup),
           "--momentum", str(args.momentum), "--seed", str(args.seed),
           "--data", args.data, "--ckpt-dir", str(ckpt_dir),
           "--ckpt-every", str(args.ckpt_every), "--resume", "auto",
           "--log-every", str(args.log_every),
           "--eval-every", str(args.eval_every),
           "--eval-batches", str(args.eval_batches),
           "--plateau-factor", str(args.plateau_factor)]
    if args.reduced:
        cmd.append("--reduced")
    if args.quant is not None:
        cmd += ["--quant", args.quant]
    if args.opt is not None:
        cmd += ["--opt", args.opt]
    if args.compress_grads:
        cmd.append("--compress-grads")
    return cmd


def _run_leg(cmd: list, tag: str, kill_at_step: int | None = None) -> int:
    """Run one training leg as a subprocess; with kill_at_step, deliver a
    real SIGTERM once the child logs that step, and expect exit 43."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env=os.environ.copy())
    killed = False
    pat = (re.compile(rf"^step\s+{kill_at_step}\b")
           if kill_at_step is not None else None)
    for line in proc.stdout:
        print(f"  [{tag}] {line}", end="", flush=True)
        if pat is not None and not killed and pat.match(line.strip()):
            proc.send_signal(signal.SIGTERM)
            killed = True
    rc = proc.wait()
    want = RESTART_EXIT_CODE if kill_at_step is not None else 0
    if kill_at_step is not None and not killed:
        raise SystemExit(f"pipeline: never saw 'step {kill_at_step}' in "
                         f"{tag} output")
    if rc != want:
        raise SystemExit(f"pipeline: {tag} exited {rc}, expected {want}")
    return rc


def _ckpt_bit_equal(a: Path, b: Path) -> bool:
    """Leaf-for-leaf bitwise comparison of two step_<n> checkpoints."""
    ma = json.loads((a / "manifest.json").read_text())
    mb = json.loads((b / "manifest.json").read_text())
    if sorted(ma["leaves"]) != sorted(mb["leaves"]):
        return False
    for key in ma["leaves"]:
        xa = np.load(a / "shard_00000" / f"{key}.npy")
        xb = np.load(b / "shard_00000" / f"{key}.npy")
        if xa.dtype != xb.dtype or xa.shape != xb.shape:
            return False
        if xa.tobytes() != xb.tobytes():
            return False
    return True


def run_rnn_pipeline(args):
    """train -> checkpoint -> SIGTERM restart -> export -> serve, asserted.

    Leg A trains with a REAL mid-run SIGTERM (delivered by this parent when
    the child logs the kill step), restarts via --resume auto, and finishes.
    Leg B trains the same command uninterrupted in a separate directory.
    The two final checkpoints must be bit-identical — that is the
    sample-exact-resume claim, proven on the actual launcher process
    boundary rather than in-process.  The trained masters then flow through
    export_packed_rnn (frozen BN) into ServeEngine with byte parity against
    the sequential oracle, and the fp-master/ternary-draft speculation pair
    measures the trained accept rate."""
    if args.quick:
        args.steps = min(args.steps, 60)
        args.eval_every = min(args.eval_every, 20)
        args.ckpt_every = min(args.ckpt_every, 10)
    args.log_every = min(args.log_every, 10)
    kill_at = max((args.steps // 2) // args.log_every, 1) * args.log_every

    made_tmp = args.ckpt_dir is None
    base = Path(args.ckpt_dir) if args.ckpt_dir else Path(
        tempfile.mkdtemp(prefix="rnn_pipeline_"))
    dir_a, dir_b = base / "interrupted", base / "straight"
    rows = []

    # --- leg A: train, SIGTERM at kill_at, restart, finish ------------------
    print(f"pipeline: leg A trains {args.steps} steps with SIGTERM at "
          f"step {kill_at}, then resumes", flush=True)
    cmd_a = _child_cmd(args, dir_a)
    t0 = time.time()
    _run_leg(cmd_a, "train-A", kill_at_step=kill_at)
    resumed_from = CK.latest_step(dir_a)
    _run_leg(cmd_a, "train-A-resume")
    # --- leg B: the uninterrupted reference ---------------------------------
    print("pipeline: leg B trains the same run uninterrupted", flush=True)
    _run_leg(_child_cmd(args, dir_b), "train-B")
    train_s = time.time() - t0

    final = f"step_{args.steps:08d}"
    exact = _ckpt_bit_equal(dir_a / final, dir_b / final)
    curve = _read_curve(dir_b / "val_curve.jsonl")
    print(f"pipeline: resume bit-exact vs uninterrupted: {exact} "
          f"(restarted from step {resumed_from})", flush=True)
    if not exact:
        raise SystemExit("pipeline: resumed run diverged from the "
                         "uninterrupted reference")
    rows.append({
        "phase": "train+restart", "steps": args.steps,
        "sigterm_at_step": kill_at, "resumed_from_step": resumed_from,
        "restart_exit_code": RESTART_EXIT_CODE,
        "resume_bit_exact": exact,
        "val_bpc_curve": [{"step": e["step"],
                           "val_bpc": round(e["val_bpc"], 4)}
                          for e in curve],
        "final_val_bpc": round(curve[-1]["val_bpc"], 4) if curve else None,
        "train_wall_s": round(train_s, 1),
    })

    # --- export the trained masters and serve them --------------------------
    print(device_banner(), flush=True)  # the legs are done: now take the chip
    from repro.core.qtensor import tree_nbytes
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.recurrent import (RNNRuntime, drive_session,
                                       speculative_draft)

    corpus = _rnn_corpus(args)
    cfg = _rnn_cfg(args, corpus)
    opt_cfg = OptConfig(kind=args.opt or "adamw", lr=args.lr,
                        momentum=args.momentum, clip_norm=1.0,
                        warmup_steps=args.warmup)
    var = BL.rnn_lm_init(jax.random.PRNGKey(args.seed), cfg)
    template = train_state_init(var["params"], opt_cfg,
                                jax.random.PRNGKey(args.seed + 1),
                                bn_state=var["state"],
                                compress=args.compress_grads)
    trained = CK.restore(template, dir_b, args.steps)

    mode = cfg.quant.mode if cfg.quant.mode != "none" else "ternary"
    qvar = BL.serving_variables(trained.params, trained.bn_state, cfg)
    fp_b, packed_b = tree_nbytes(qvar["params"])
    rt_packed = RNNRuntime(cfg, qvar)
    print(f"pipeline: exported packed {mode} weights "
          f"({fp_b/1e6:.2f} MB fp32 -> {packed_b/1e6:.2f} MB, "
          f"{fp_b/max(packed_b,1):.1f}x), BN statistics frozen", flush=True)

    # byte parity: the engine's per-request streams vs the sequential oracle
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(2, 10))),
                    max_tokens=int(rng.integers(2, 9)),
                    temperature=0.8, top_k=5, seed=100 + i, rid=i)
            for i in range(5)]
    eng = ServeEngine(rt_packed, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4)
    comps, _ = eng.run([dataclasses.replace(r) for r in reqs],
                       realtime=False)
    by_rid = {c.rid: c for c in comps}
    for r in reqs:
        out, _ = drive_session(
            rt_packed, jnp.asarray(np.asarray(r.prompt, np.int32))[None],
            cfg.vocab, gen=r.max_tokens, temperature=r.temperature,
            top_k=r.top_k, seed=r.seed)
        if by_rid[r.rid].tokens != out[0].tolist():
            raise SystemExit(f"pipeline: engine stream for request {r.rid} "
                             "diverged from the sequential oracle")
    print(f"pipeline: ServeEngine byte parity vs sequential oracle over "
          f"{len(reqs)} requests", flush=True)
    rows.append({"phase": "export+serve", "quant": mode,
                 "fp32_mb": round(fp_b / 1e6, 3),
                 "packed_mb": round(packed_b / 1e6, 3),
                 "engine_byte_parity": True, "parity_requests": len(reqs)})

    # trained-master speculation: fp target, packed draft, greedy drain
    fp_cfg = dataclasses.replace(cfg, quant=QuantSpec(mode="none"))
    rt_fp = RNNRuntime(fp_cfg, {"params": trained.params,
                                "state": trained.bn_state})
    draft = speculative_draft(rt_fp, mode=mode)
    prompt_len, gen, spec_k = 6, 32 if args.quick else 48, 4
    greedy = [Request(prompt=rng.integers(0, cfg.vocab, size=prompt_len),
                      max_tokens=gen, temperature=0.0, top_k=0,
                      seed=500 + i, rid=i) for i in range(4)]
    lens = [prompt_len] * len(greedy)
    ctx = prompt_len + gen
    plain = ServeEngine(rt_fp, cfg.vocab, slots=1, max_context=ctx,
                        prefill_chunk=8)
    spec = ServeEngine(rt_fp, cfg.vocab, slots=1, max_context=ctx,
                       prefill_chunk=8, draft=draft, spec_k=spec_k)
    plain.warm(lens)
    spec.warm(lens)
    _, mp = plain.run([dataclasses.replace(r) for r in greedy],
                      realtime=False)
    _, ms = spec.run([dataclasses.replace(r) for r in greedy],
                     realtime=False)
    print(f"pipeline: trained-master speculation k={spec_k} accept rate "
          f"{ms['accept_rate']:.3f}, {ms['agg_tok_s']:.0f} tok/s spec vs "
          f"{mp['agg_tok_s']:.0f} plain", flush=True)
    if not args.quick:
        assert ms["accept_rate"] > 0.6, ms["accept_rate"]
    rows.append({"phase": "speculation", "spec_k": spec_k,
                 "accept_rate": round(ms["accept_rate"], 3),
                 "drafted_tokens": ms["drafted_tokens"],
                 "plain_tok_s": round(mp["agg_tok_s"], 1),
                 "spec_tok_s": round(ms["agg_tok_s"], 1),
                 "speedup_vs_plain": round(ms["agg_tok_s"]
                                           / max(mp["agg_tok_s"], 1e-9), 2),
                 "asserted": not args.quick})

    RESULTS.mkdir(parents=True, exist_ok=True)
    payload = {"meta": {"arch": args.arch, "reduced": args.reduced,
                        "hidden": cfg.d_hidden, "vocab": cfg.vocab,
                        "cell": cfg.cell, "quant": cfg.quant.mode,
                        "corpus": args.data, "steps": args.steps,
                        "batch": args.batch, "seq": args.seq,
                        "opt": opt_cfg.kind, "lr": args.lr,
                        "quick": args.quick,
                        "backend": jax.default_backend(),
                        "note": "reduced-scale synthetic corpus: relative "
                                "claims only; absolute BPC is not "
                                "comparable to the paper's tables"},
               "rows": rows}
    out = RESULTS / "train_rnn.json"
    out.write_text(json.dumps(payload, indent=1))
    print(f"pipeline: wrote {out}", flush=True)
    if made_tmp:
        import shutil
        shutil.rmtree(base, ignore_errors=True)
    return rows


# ---------------------------------------------------------------------------
# transformer pool
# ---------------------------------------------------------------------------


def run_transformer(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant is not None:
        cfg = cfg.with_quant(QuantSpec(mode=args.quant, norm="channel")
                             if args.quant != "none" else QuantSpec(mode="none"))

    # --- data --------------------------------------------------------------
    if args.data == "synthetic":
        vocab = cfg.vocab
        make_batch = lambda s: token_stream(s, args.batch, args.seq, vocab,
                                            seed=args.seed)
    else:
        p = Path(args.data)
        corpus = (ByteCorpus.from_dir(p) if p.is_dir()
                  else ByteCorpus.from_files([p]))
        if corpus.vocab > cfg.vocab:
            raise SystemExit(f"corpus vocab {corpus.vocab} > model {cfg.vocab}")
        make_batch = lambda s: corpus.batch("train", s, args.batch, args.seq)

    # --- mesh (elastic: derive from live devices) ---------------------------
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        plan = best_mesh_shape(n_dev, want_model=args.mesh_model,
                               global_batch=args.batch)
        mesh = make_mesh_from_plan(plan)
        print(f"mesh: {dict(zip(plan.axes, plan.shape))}, "
              f"per-replica batch {plan.per_replica_batch}, "
              f"dropped {plan.dropped_devices} devices")

    opt_cfg = OptConfig(kind=args.opt or "adamw", lr=args.lr,
                        momentum=args.momentum, warmup_steps=args.warmup,
                        decay_steps=args.steps, clip_norm=1.0)

    params = T.model_init(jax.random.PRNGKey(args.seed), cfg)
    state = train_state_init(params, opt_cfg, jax.random.PRNGKey(args.seed + 1),
                             compress=args.compress_grads)
    step_fn = make_train_step(cfg, opt_cfg, mesh=mesh,
                              compress_grads=args.compress_grads)

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CK.AsyncCheckpointer(args.ckpt_dir)
        if args.resume == "auto" and CK.latest_step(args.ckpt_dir) is not None:
            start_step = CK.latest_step(args.ckpt_dir)
            state = CK.restore(state, args.ckpt_dir, start_step)
            print(f"resumed from step {start_step}")

    if mesh is not None:
        st_sh = state_shardings(state, mesh)
        b_sh = batch_shardings(make_batch(0), mesh)
        jstep = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, None))
    else:
        jstep = jax.jit(step_fn)

    handler = PreemptionHandler()
    monitor = StragglerMonitor(n_hosts=jax.process_count())
    prefetch = Prefetcher(make_batch, start_step, mesh=mesh)

    t_start = time.time()
    with use_mesh(mesh, param_rules=param_pspec):
        for step, batch in prefetch:
            if step >= args.steps:
                break
            with StepTimer() as tm:
                state, metrics = jstep(state, batch)
                jax.block_until_ready(metrics["loss"])
            monitor.record(jax.process_index(), tm.dt)

            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                print(f"step {step:6d} loss {loss:.4f} "
                      f"lr {float(metrics.get('lr', 0)):.2e} "
                      f"gnorm {float(metrics.get('grad_norm', 0)):.2f} "
                      f"{tm.dt*1e3:.0f} ms", flush=True)
            # checkpoint index == COMPLETED steps (step+1): a restart resumes
            # at the next step and replays nothing — the same convention as
            # the preemption path below, so periodic and preemption restores
            # are both sample-exact
            done = step + 1
            if ckpt and done % args.ckpt_every == 0 and done < args.steps:
                ckpt.save_async(state, done)
            if handler.preempted:
                print("preempted: checkpointing and exiting 43", flush=True)
                if ckpt:
                    ckpt.wait()
                    CK.save(state, args.ckpt_dir, done)
                prefetch.close()
                sys.exit(RESTART_EXIT_CODE)

    prefetch.close()
    if ckpt:
        ckpt.wait()
        CK.save(state, args.ckpt_dir, args.steps)
    dt = time.time() - t_start
    print(f"done: {args.steps - start_step} steps in {dt:.1f}s "
          f"({(args.steps - start_step) / max(dt, 1e-9):.2f} steps/s)")
    return state


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if not args.pipeline:  # the pipeline's legs are child processes: the
        print(device_banner(), flush=True)  # parent must not hold the chip
    if args.arch in RNN_ARCH_IDS:
        return run_rnn_pipeline(args) if args.pipeline else run_rnn(args)
    if args.pipeline:
        raise SystemExit("--pipeline is the rnn-paper train->serve proof; "
                         "run it with --arch rnn-paper")
    return run_transformer(args)


if __name__ == "__main__":
    enable_compile_cache()
    main()
