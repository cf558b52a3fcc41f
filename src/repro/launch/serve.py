"""Serving launcher: prefill a prompt batch, decode with sampling.

  python -m repro.launch.serve --arch qwen3-0.6b --reduced --gen 32 --batch 4
  python -m repro.launch.serve --arch rnn-paper --quant ternary
  python -m repro.launch.serve --arch rnn-paper --traffic --rate 8 \
      --requests 32 --slots 8

Every arch — the transformer pool AND the paper's own BN-LSTM — runs the
same prefill → sample → decode loop through the unified recurrent runtime
(serve/recurrent.py).  With --quant binary|ternary the trained-master tree
is exported ONCE into packed `QTensor`s (core/qtensor.py) and prefill/decode
stream the packed codes through the Pallas kernels — the reported packed MB
is the memory the decode loop actually reads, not an analytic estimate.
For --arch rnn-paper the per-step work is the whole-tick fused kernel
(kernels/decode_step.py): ONE launch per token for all layers + head on
accelerators, the compiled dense fallback on CPU (DESIGN.md §11).  On a pod
the same entry point runs under the production mesh with the decode-time
cache shardings from launch/sharding.py.

--traffic switches from the lockstep batch to the continuous-batching
engine (serve/engine.py): a synthetic Poisson workload with mixed prompt
and generation lengths is replayed against a fixed slot pool, requests are
admitted as slots free up, and the report is aggregate tok/s, slot
occupancy and p50/p95 per-request latency — the serving numbers a fleet
actually provisions against.

--spec-k N adds speculative decoding to --traffic: the fp master tree is
the TARGET and its own packed binary/ternary export (the --quant mode) the
DRAFT — each round the draft proposes N tokens per slot, the target
verifies them in one multi-token step, and rejection sampling keeps the
output distribution exactly the target's (byte-identical at temperature
0).  The report adds the measured acceptance rate and the drafted-token
throughput next to the emitted tok/s.

--listen swaps the synthetic workload for the HTTP/SSE front door
(serve/frontdoor.py): the same warmed engine behind POST /v1/generate and
GET /v1/stats, with client disconnects cancelling mid-flight and (where the
runtime supports it) a prefix-state cache sized by --prefix-cache-mb
serving repeated system prompts from one spliced row copy.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import (ARCH_IDS, RNN_ARCH_IDS, get_config, get_rnn_config,
                           rnn_paper)
from repro.core import bnlstm as BL
from repro.core.qtensor import export_packed, tree_nbytes
from repro.core.quantize import QuantSpec
from repro.models import transformer as T
from repro.runtime import device_banner, enable_compile_cache
from repro.serve.engine import Request, ServeEngine
from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                   drive_session, speculative_draft)


def packed_model_bytes(qparams) -> tuple[int, int]:
    """(fp32-equivalent bytes, actual bytes) of an exported serving tree —
    measured from the real `QTensor.nbytes`, not the analytic formula."""
    return tree_nbytes(qparams)


def _report_bytes(rt, quant: str) -> None:
    fp, packed = rt.param_nbytes()
    print(f"model bytes: fp32 {fp/1e6:.1f} MB -> packed({quant}) "
          f"{packed/1e6:.1f} MB ({fp/packed:.1f}x smaller)")


def _build_rnn(args, key):
    """The paper's BN-LSTM/GRU behind the same serving loop."""
    cfg = get_rnn_config(args.arch)
    if args.reduced:
        cfg = rnn_paper.reduced(cfg)
    spec = (QuantSpec(mode=args.quant, norm="batch")
            if args.quant != "none" else QuantSpec(mode="none"))
    cfg = dataclasses.replace(cfg, quant=spec)
    var = BL.rnn_lm_init(key, cfg)
    params = var["params"]
    if args.quant != "none":
        params = BL.export_packed_rnn(params, cfg)
    rt = RNNRuntime(cfg, {"params": params, "state": var["state"]})
    if args.quant != "none":
        _report_bytes(rt, args.quant)
    return cfg, rt


def _build_transformer(args, key):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.with_quant(QuantSpec(mode=args.quant, norm="channel")
                         if args.quant != "none" else QuantSpec(mode="none"))
    params = T.model_init(key, cfg)
    if args.quant != "none":
        # the train->serve handoff: masters -> packed QTensors, once.  The
        # decode loop below runs against THIS tree, so the printed packed MB
        # is what the matmuls stream.
        params = export_packed(params, cfg.quant)
    B, S = args.batch, args.prompt_len
    extras = {}
    if cfg.family == "vlm":
        extras["img"] = jax.random.normal(key, (B, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "audio":
        extras["enc_frames"] = jax.random.normal(key, (B, S, cfg.d_model))
    rt = TransformerRuntime(cfg, params, extras=extras)
    if args.quant != "none":
        _report_bytes(rt, args.quant)
    return cfg, rt


def synth_traffic(vocab: int, *, requests: int, rate: float, prompt_len: int,
                  gen: int, temperature: float, top_k: int,
                  seed: int = 0) -> list:
    """A synthetic mixed-length workload: Poisson arrivals at `rate` req/s,
    prompt lengths U[1, prompt_len], generation lengths U[1, gen] — the
    mixed-depth traffic continuous batching exists for.  Deterministic in
    `seed` so a workload can be replayed across engines / PRs."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=requests)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(requests):
        S = int(rng.integers(1, prompt_len + 1))
        n = int(rng.integers(1, gen + 1))
        reqs.append(Request(
            prompt=rng.integers(0, vocab, size=S),
            max_tokens=n, temperature=temperature, top_k=top_k,
            seed=seed + 1000 + i, arrival_s=float(arrivals[i]), rid=i))
    return reqs


def _serve_mesh(args):
    """Resolve --mesh into a Mesh (None when unset) and announce it."""
    if not getattr(args, "mesh", ""):
        return None
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(args.mesh)
    shape = ",".join(f"{a}={n}" for a, n in mesh.shape.items())
    print(f"mesh: {shape} over {mesh.size} device(s) — slot pool sharded "
          f"along 'data', weights tensor-parallel along 'model'")
    return mesh


def run_traffic(cfg, rt, args, draft=None) -> dict:
    """Replay a Poisson workload through the continuous-batching engine."""
    ctx = args.prompt_len + args.gen
    eng = ServeEngine(rt, cfg.vocab, slots=args.slots, max_context=ctx,
                      prefill_chunk=args.prefill_chunk,
                      draft=draft, spec_k=args.spec_k if draft else 0,
                      mesh=_serve_mesh(args))
    reqs = synth_traffic(cfg.vocab, requests=args.requests, rate=args.rate,
                         prompt_len=args.prompt_len, gen=args.gen,
                         temperature=args.temperature, top_k=args.top_k,
                         seed=args.seed)
    # warm the tick and every declared prefill chunk bucket before the
    # clock starts, so latency percentiles measure serving, not XLA
    # compilation (one prefill trace per bucket; the tick never retraces)
    eng.warm([np.asarray(r.prompt).size for r in reqs])
    comps, m = eng.run(reqs, realtime=True)
    print(f"traffic: {m['requests']} requests over {m['wall_s']:.2f}s "
          f"({args.rate:.1f} req/s offered, {args.slots} slots, "
          f"prefill chunk {args.prefill_chunk})")
    # a spec round replaces the plain tick: its draft step's launches count
    launches = (f"draft Pallas launches per step: {m['draft_launches']}"
                if draft is not None else
                f"Pallas launches per tick: {m['tick_launches']}")
    print(f"aggregate decode: {m['agg_tok_s']:.1f} tok/s  "
          f"occupancy: {100 * m['occupancy']:.0f}%  "
          f"ticks: {m['ticks']} (traces: {m['tick_traces']}, {launches}, "
          f"prefill traces: {m['prefill_traces']})")
    print(f"latency: p50 {m['p50_latency_s']*1e3:.0f} ms  "
          f"p95 {m['p95_latency_s']*1e3:.0f} ms  |  "
          f"ttft: p50 {m['ttft_p50_s']*1e3:.0f} ms  "
          f"p95 {m['ttft_p95_s']*1e3:.0f} ms  "
          f"(max decode stall: {m['max_decode_stall_ticks']} chunk)")
    if draft is not None:
        print(f"speculative: k={m['spec_k']}  "
              f"accept rate {100 * m['accept_rate']:.0f}%  "
              f"({m['accepted_drafts']}/{m['drafted_tokens']} drafts over "
              f"{m['spec_rounds']} rounds)  "
              f"draft {m['draft_tok_s']:.1f} tok/s proposed")
    done = sorted(comps, key=lambda c: c.rid)[:4]
    for c in done:
        print(f"  req {c.rid}: prompt {c.prompt_len} -> {len(c.tokens)} toks "
              f"({c.finished}), ttft {c.ttft_s*1e3:.0f} ms, "
              f"latency {c.latency_s*1e3:.0f} ms")
    return m


def run_listen(cfg, rt, args, draft=None) -> None:
    """Serve over HTTP/SSE: build the engine the way --traffic does (same
    warm, same invariants), hand it to the asyncio front door, block."""
    import asyncio

    from repro.serve.frontdoor import FrontDoor
    from repro.serve.prefixcache import PrefixCache

    ctx = args.prompt_len + args.gen
    cache = None
    if args.prefix_cache_mb > 0:
        supported = (getattr(rt, "chunk_granularity", "whole") == "token"
                     and (rt.family == "rnn"
                          or getattr(rt, "pad_buckets", False)))
        if supported:
            cache = PrefixCache(args.prefix_cache_mb << 20)
        else:
            print("prefix cache: unsupported for this runtime "
                  "(needs token-granularity chunking; non-ring caches) "
                  "— serving without it")
    eng = ServeEngine(rt, cfg.vocab, slots=args.slots, max_context=ctx,
                      prefill_chunk=args.prefill_chunk,
                      draft=draft, spec_k=args.spec_k if draft else 0,
                      prefix_cache=cache, mesh=_serve_mesh(args))
    eng.warm([args.prompt_len])

    async def _serve():
        fd = FrontDoor(eng, host=args.host, port=args.port)
        await fd.start()
        print(f"front door listening on http://{fd.host}:{fd.port}  "
              f"({args.slots} slots, ctx {ctx}, chunk {args.prefill_chunk}"
              + (f", prefix cache {args.prefix_cache_mb} MB" if cache
                 else "") + ")")
        print(f"  curl -N -X POST http://{fd.host}:{fd.port}/v1/generate "
              "-d '{\"prompt\": [1,2,3], \"max_tokens\": 16}'")
        try:
            await fd.serve_forever()
        finally:
            await fd.close()

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + RNN_ARCH_IDS,
                    default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--quant", default="ternary",
                    choices=("none", "binary", "ternary"))
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--traffic", action="store_true",
                    help="replay a mixed-length Poisson workload through "
                         "the continuous-batching ServeEngine")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="offered arrival rate, requests/s (--traffic)")
    ap.add_argument("--requests", type=int, default=16,
                    help="workload size (--traffic)")
    ap.add_argument("--slots", type=int, default=4,
                    help="engine slot-pool size (--traffic)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="in-slot prefill chunk size: at most one chunk "
                         "runs between decode ticks, so long prompts never "
                         "stall live decodes (--traffic)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: the packed --quant export "
                         "of the model drafts K tokens per round for the "
                         "fp target to verify (--traffic only; 0 = off)")
    ap.add_argument("--listen", action="store_true",
                    help="serve the engine over HTTP/SSE "
                         "(serve/frontdoor.py) instead of replaying a "
                         "synthetic workload")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8700)
    ap.add_argument("--prefix-cache-mb", type=int, default=64,
                    help="prefix-state cache byte budget for --listen "
                         "(0 = off); repeated system prompts resume from "
                         "a spliced state row instead of re-prefilling")
    ap.add_argument("--mesh", default="",
                    help="serve on a device mesh, e.g. 'data=4,model=2': "
                         "slot pool sharded D-way along 'data' (slots must "
                         "divide D), weights tensor-parallel along 'model' "
                         "(DESIGN.md §12); on CPU run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args(argv)

    if args.spec_k and not (args.traffic or args.listen):
        raise SystemExit("--spec-k is a continuous-batching engine mode; "
                         "run it with --traffic or --listen")
    if args.mesh and not (args.traffic or args.listen):
        raise SystemExit("--mesh shards the continuous-batching engine; "
                         "run it with --traffic or --listen")
    print(device_banner(), flush=True)
    key = jax.random.PRNGKey(args.seed)
    build = _build_rnn if args.arch in RNN_ARCH_IDS else _build_transformer
    draft = None
    if args.spec_k:
        # self-speculation: the fp masters ARE the target; --quant names
        # the DRAFT's packing (the default ternary when unset)
        draft_mode = args.quant if args.quant != "none" else "ternary"
        args.quant = "none"
        cfg, rt = build(args, key)
        draft = speculative_draft(rt, mode=draft_mode)
        _report_bytes(draft, draft_mode)
    else:
        cfg, rt = build(args, key)

    if args.listen:
        return run_listen(cfg, rt, args, draft=draft)
    if args.traffic:
        return run_traffic(cfg, rt, args, draft=draft)

    B, S = args.batch, args.prompt_len
    prompt = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab)
    out, m = drive_session(rt, prompt, cfg.vocab, gen=args.gen,
                           temperature=args.temperature, top_k=args.top_k,
                           seed=args.seed + 1)
    print(f"session state: {m['state_nbytes']/1e6:.2f} MB "
          f"({rt.family} family)")
    print(f"prefill: {m['prefill_tok_s']:.0f} tok/s  "
          f"decode: {m['decode_tok_s']:.1f} tok/s")
    print(f"generated ids[0,:16]: {out[0, :16].tolist()}")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
