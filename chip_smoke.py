"""Drive the paper's BN-LSTM through its main path on one TPU chip.

    python chip_smoke.py               # one chip: serving, logits, training
    python chip_smoke.py --four-chips  # four chips: data=4 mesh serving only

One process, the package's own entry points, full published width
(`rnn-paper` = char-PTB, H=1000, vocab 50; weights random from SEED).
Phases, each of which fails loudly:

  1. device    the first JAX device must be a TPU, otherwise exit 2;
  2. serving   `repro.launch.serve.main --arch rnn-paper --full --traffic`
               in ternary and in binary: every request finishes, the decode
               tick traces once and dispatches ONE Pallas launch; then the
               fp target with its packed ternary export as speculative
               draft (--spec-k 4): every request finishes, one spec trace
               whose draft step is ONE Pallas launch (the fused tick);
  3. logits    the same exported weights served packed (prefill + 16
               greedy decode ticks at B=8) against a float32 reference,
               `rnn_lm_apply` over the dequantized weights under
               `jax.default_matmul_precision("highest")`: largest absolute
               logit difference <= LOGIT_RTOL x the largest reference
               logit, and identical greedy tokens;
  4. training  `repro.launch.train.main --arch rnn-paper`, a few steps at
               batch 32, seq 100; every loss finite (the launcher stops on a
               non-finite one) and the trained weights finite.

--four-chips runs only the mesh path: the fp `rnn-paper` ServeEngine over
`make_serve_mesh("data=4")` against a single-device engine on the same
workload, with the slot pool checked to be split over four devices and the
token streams asserted byte-identical.

Tok/s lines are information, not a benchmark.  The last line of standard
output is {"ok": true, "device": {"platform", "kind", "count"}}; nothing
prints it unless every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

SEED = 0
# Largest admitted |served - reference| logit, relative to max |reference|.
# Served packed and reference fp32 differ only in summation order: a TPU v5e
# reads about 4e-7 of the logit scale.  With the head's or qmatmul's fp32
# dots at DEFAULT precision (one bf16 pass) it reads about 4e-3 of it, while
# the greedy tokens still all agree, so only this limit catches that fault.
LOGIT_RTOL = 1e-5


def _fail(msg: str, code: int = 1):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def device_phase(need: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:  # no backend at all: same verdict as a CPU
        _fail(f"no TPU found ({e})", 2)
    if devs[0].platform != "tpu":
        _fail(f"no TPU found: JAX runs on {devs[0].platform!r}", 2)
    if len(devs) < need:
        _fail(f"need {need} TPU devices, JAX sees {len(devs)}", 2)
    from repro.kernels import dispatch

    # the served path must run compiled kernels, never interpret-mode
    # Pallas or the dense CPU fallback
    assert not dispatch.resolve_interpret(None)
    assert not dispatch.prefer_dense(None)
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def serving_phase(mode: str, seed: int, spec_k: int = 0) -> None:
    """spec_k > 0 serves the fp target with its packed `mode` export as the
    speculative draft (the draft's fused tick runs inside a scan)."""
    from repro.launch import serve

    n = 16
    tag = f"{mode}, packed draft k={spec_k}" if spec_k else mode
    m = serve.main(["--arch", "rnn-paper", "--full", "--traffic",
                    "--quant", mode, "--slots", "8", "--requests", str(n),
                    "--prompt-len", "64", "--gen", "32", "--rate", "16",
                    "--prefill-chunk", "32", "--seed", str(seed),
                    "--spec-k", str(spec_k)])
    if m["requests"] != n:
        _fail(f"serving {tag}: {m['requests']} of {n} requests finished")
    if spec_k:
        if m["spec_traces"] != 1 or m["draft_launches"] != 1:
            _fail(f"serving {tag}: spec round traced {m['spec_traces']}x "
                  f"with {m['draft_launches']} Pallas launches per draft "
                  f"step, want 1 and 1")
        inv = (f"spec_traces=1 draft_launches=1 accept rate "
               f"{m['accept_rate']:.3f}")
    elif m["tick_traces"] != 1 or m["tick_launches"] != 1:
        _fail(f"serving {tag}: tick traced {m['tick_traces']}x with "
              f"{m['tick_launches']} Pallas launches, want 1 and 1")
    else:
        inv = "tick_traces=1 tick_launches=1"
    print(f"[serving {tag}] {n}/{n} requests finished, {m['gen_tokens']} "
          f"tokens, {inv} ({m['agg_tok_s']:.1f} tok/s, information only)",
          flush=True)


def _walked_state(state, key):
    """BN running statistics moved off their init values, so the folded
    serving affines are non-trivial (the variances stay near 1)."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(state)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
               for l, k in zip(leaves, keys)])


def logits_phase(mode: str, seed: int, B: int = 8, S: int = 32,
                 steps: int = 16) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import rnn_paper
    from repro.core import bnlstm as BL
    from repro.core.qtensor import is_qtensor
    from repro.core.quantize import QuantSpec
    from repro.serve.recurrent import RNNRuntime

    cfg = rnn_paper.char_ptb(mode=mode)
    k_init, k_bn, k_tok = jax.random.split(jax.random.PRNGKey(seed), 3)
    var = BL.rnn_lm_init(k_init, cfg)
    bn = _walked_state(var["state"], k_bn)
    packed = BL.export_packed_rnn(var["params"], cfg)

    rt = RNNRuntime(cfg, {"params": packed, "state": bn})
    assert "tick" in rt.tables[0], "packed runtime built no fused tick"
    prompt = jax.random.randint(k_tok, (B, S), 0, cfg.vocab, jnp.int32)
    lg, st = rt.prefill(prompt, rt.init_state(B))
    served, toks = [lg], []
    for _ in range(steps):
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        toks.append(tok)
        lg, st = rt.decode_step(tok, st)
        served.append(lg)
    served = np.asarray(jnp.stack(served, axis=1))       # (B, steps+1, V)
    toks = np.asarray(jnp.stack(toks, axis=1))           # (B, steps)

    # reference: the plain fp32 forward over the dequantized weights
    fp = jax.tree.map(lambda l: l.dequantize(jnp.float32)
                      if is_qtensor(l) else l, packed, is_leaf=is_qtensor)
    ref_cfg = dataclasses.replace(cfg, quant=QuantSpec(mode="none"))
    seq = jnp.concatenate([prompt, jnp.asarray(toks)], axis=1)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda v, t: BL.rnn_lm_apply(
            v, t, ref_cfg, training=False))({"params": fp, "state": bn}, seq)
    ref = np.asarray(ref)[:, S - 1:]                     # (B, steps+1, V)

    if not np.isfinite(served).all():
        _fail(f"logits {mode}: served logits are not finite")
    diff = float(np.max(np.abs(served - ref)))
    scale = float(np.max(np.abs(ref)))
    tol = LOGIT_RTOL * scale
    ref_greedy = ref[:, :steps].argmax(-1)
    same = int((ref_greedy == toks).sum())
    print(f"[logits {mode}] prefill + {steps} decode steps at B={B}: max "
          f"|served - fp32 reference| = {diff:.3e}, max |reference| = "
          f"{scale:.3e}, tol {tol:.3e} ({LOGIT_RTOL:g} of it); greedy "
          f"tokens identical {same}/{toks.size}", flush=True)
    if not diff <= tol:
        _fail(f"logits {mode}: max abs difference {diff:.3e} > {tol:.3e}")
    if same != toks.size:
        _fail(f"logits {mode}: greedy tokens differ from the reference")


def training_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train

    steps = 5
    state = train.main(["--arch", "rnn-paper", "--steps", str(steps),
                        "--batch", "32", "--seq", "100", "--log-every", "1",
                        "--eval-every", str(steps), "--eval-batches", "1",
                        "--seed", str(seed)])
    bad = [l for l in jax.tree_util.tree_leaves(state.params)
           if not np.isfinite(np.asarray(l)).all()]
    if bad:
        _fail(f"training: {len(bad)} parameter leaves are not finite")
    print(f"[training] {steps} steps at H=1000, batch 32, seq 100: every "
          f"loss and weight finite", flush=True)


def four_chip_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import rnn_paper
    from repro.core import bnlstm as BL
    from repro.core.quantize import QuantSpec
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import synth_traffic
    from repro.serve.engine import ServeEngine
    from repro.serve.recurrent import RNNRuntime

    cfg = dataclasses.replace(rnn_paper.char_ptb(),
                              quant=QuantSpec(mode="none"))
    var = BL.rnn_lm_init(jax.random.PRNGKey(seed), cfg)
    rt = RNNRuntime(cfg, var)
    reqs = synth_traffic(cfg.vocab, requests=16, rate=16.0, prompt_len=64,
                         gen=32, temperature=0.8, top_k=40, seed=seed)
    lens = [np.asarray(r.prompt).size for r in reqs]
    streams = {}
    for spec in ("data=4", ""):
        mesh = make_serve_mesh(spec) if spec else None
        eng = ServeEngine(rt, cfg.vocab, slots=8, max_context=96,
                          prefill_chunk=32, mesh=mesh)
        if mesh is not None:
            shards = eng.pool.h.addressable_shards
            devs = {s.device for s in shards}
            rows = sorted(s.data.shape[1] for s in shards)
            print(f"[mesh data=4] pool h {eng.pool.h.shape} over "
                  f"{len(devs)} devices, slot rows per shard {rows}",
                  flush=True)
            if len(devs) != 4 or rows != [2, 2, 2, 2]:
                _fail("the slot pool is not split over the 4 devices")
        eng.warm(lens)
        comps, m = eng.run([dataclasses.replace(r) for r in reqs],
                           realtime=False)
        if m["requests"] != len(reqs) or eng.tick_traces != 1:
            _fail(f"mesh {spec or 'none'}: {m['requests']} requests, "
                  f"{eng.tick_traces} tick traces")
        streams[spec] = {c.rid: c.tokens for c in comps}
        print(f"[mesh {spec or 'single device'}] {m['requests']} requests, "
              f"{m['gen_tokens']} tokens, tick_traces=1 "
              f"({m['agg_tok_s']:.1f} tok/s, information only)", flush=True)
    if streams["data=4"] != streams[""]:
        bad = [r for r in streams[""] if streams["data=4"][r] != streams[""][r]]
        _fail(f"data=4 streams differ from single-device for requests {bad}")
    print(f"[mesh] data=4 and single-device token streams byte-identical "
          f"over {len(reqs)} requests", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=4 mesh serving parity check")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        _fail(f"the repro package is not next to this script (no {src})")
    sys.path.insert(0, str(src))
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    devs = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(SEED)
    else:
        for mode in ("ternary", "binary"):
            serving_phase(mode, SEED)
        serving_phase("ternary", SEED, spec_k=4)
        for mode in ("ternary", "binary"):
            logits_phase(mode, SEED)
        training_phase(SEED)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
