"""One run of one cell: set-up, pre-roll, the measured window, the reading
of the trace, and the comparison that decides `correct`.

The window drives the program's `ServeEngine.submit()` / `step()` and
stamps every token event on its own clock when `step()` returns it.
Nothing compiles inside the window: the engine's trace counters are read
before and after it, and a run whose counters moved fails."""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import spec as SPEC
from bench import traffic as TR

TRACE_DIR = SPEC.ROOT / ".bench_trace"
TRACE_SECONDS = 8.0       # the traced part of a --trace 1 window, at most
GREEDY_ROWS = 8           # greedy requests the check compares, at most
GREEDY_TOKENS = 1024      # ... and enough of them for this many tokens


class RunFailed(Exception):
    """The run cannot give a result (no chip, a compile in the window)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def nearest_rank(values, q: float) -> Optional[float]:
    if not len(values):
        return None
    v = sorted(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


@dataclasses.dataclass
class Track:
    req: TR.Req
    due: float                  # clock seconds the request was due
    submitted: float
    first: Optional[float] = None
    last: Optional[float] = None
    done: bool = False
    tokens: Optional[list] = None


class Window:
    """The harness's record of one window: token events, gaps, lags."""

    def __init__(self):
        self.track: Dict[int, Track] = {}
        self.t0 = self.t1 = 0.0
        self.tokens = 0
        self.itl: List[float] = []
        self.steps = 0
        self.tick_events = 0       # tokens of plain decode ticks
        self.prompt_tokens = 0     # prompts whose prefill ended inside
        self.lags: List[float] = []
        self.outstanding = 0       # submitted and not finished

    def inside(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Feed:
    """The requests still to come, (due, Req) in order, one looked at."""

    def __init__(self, items):
        self._it = iter(items)
        self.head = next(self._it, None)

    def pop(self):
        head, self.head = self.head, next(self._it, None)
        return head


def _submit(eng, req):
    from repro.serve.engine import Request
    return eng.submit(Request(prompt=req.prompt, max_tokens=req.max_tokens,
                              temperature=req.temperature, top_k=req.top_k,
                              seed=req.seed))


def drive(eng, w: Window, until: float, feed: Feed, clock, *, slots: int,
          closed: bool, waiting: int, on_done=None):
    """Run the scheduler until `clock() >= until`.  A closed loop keeps
    slots + waiting requests outstanding; an open loop submits each request
    of `feed` once it is due."""
    while True:
        now = clock()
        if now >= until:
            return
        with _span("bench.submit"):
            while feed.head is not None:
                if closed:
                    if w.outstanding >= slots + waiting:
                        break
                    due = now
                else:
                    due = feed.head[0]
                    if due > now:
                        break
                req = feed.pop()[1]
                rid = _submit(eng, req)
                t = clock()
                w.track[rid] = Track(req=req, due=due, submitted=t)
                w.outstanding += 1
                if w.inside(due):
                    w.lags.append(t - due)
        if eng.has_work():
            with _span("bench.step"):
                events, comps = eng.step()
            t = clock()
            with _span("bench.collect"):
                w.steps += 1
                ins = t >= w.t0
                for rid, toks in events:
                    tr = w.track[rid]
                    if tr.first is None:
                        tr.first = t
                        if ins:
                            w.prompt_tokens += tr.req.prompt.size
                    else:
                        if ins:
                            w.tick_events += len(toks)
                        if ins and tr.last >= w.t0:
                            w.itl.append(t - tr.last)
                    tr.last = t
                    if ins:
                        w.tokens += len(toks)
                for c in comps:
                    tr = w.track[c.rid]
                    tr.done = True
                    w.outstanding -= 1
                    tr.tokens = list(c.tokens)
                    if on_done is not None:
                        on_done(c)
        else:
            wake = until if feed.head is None else min(until, feed.head[0])
            with _span("bench.wait_arrival"):
                time.sleep(max(0.0, min(wake - clock(), 0.05)))


def _device_check(chips: int, require_chip: bool):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise RunFailed(f"JAX finds no device ({e})")
    if require_chip:
        if devs[0].platform != "tpu":
            raise RunFailed(f"JAX runs on {devs[0].platform!r}, not on a TPU")
        if len(devs) < chips:
            raise RunFailed(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    return devs


def serve_window(eng, cfg, tp, seed, seconds, slots, *, trace, trace_seconds,
                 t_start):
    """Pre-roll, lead-in and the measured window of one traffic mix.
    Returns the window's record, its end-to-end metrics, the counts of the
    traced part, and the feed of requests still to come."""
    import jax

    clock = time.perf_counter
    closed = tp["loop"] == "closed"
    w = Window()
    w.t0 = w.t1 = float("inf")
    # pre-roll: one staggered request per slot; a closed loop keeps its
    # queue full meanwhile, so each slot is refilled as it retires
    waiting = int(tp.get("waiting", 0))
    feed = Feed([(0.0, r) for r in TR.closed_pool(tp, seed, cfg["vocab"])]
                if closed else [])
    preroll(eng, w, TR.preroll(tp, seed, cfg["vocab"], slots), feed, clock,
            slots=slots, closed=closed, waiting=waiting)
    if not closed:
        sched = TR.open_schedule(tp, seed, cfg["vocab"], seconds)
        origin = clock() + float(tp["lead_in_s"])
        feed = Feed([(origin + due, r) for due, r in sched])
        # lead-in: arrivals before the window, served and not measured
        drive(eng, w, origin, feed, clock, slots=slots, closed=False,
              waiting=0)

    tt0, pt0 = eng.tick_traces, eng.prefill_traces
    w.t0 = clock()
    setup_s = w.t0 - t_start
    w.t1 = w.t0 + seconds
    traced = None
    # a traced run traces the last `trace_seconds` of its window: tracing
    # slows the host, and an open loop's queue would carry that into
    # every later request
    tw = min(seconds, trace_seconds) if trace else 0.0
    drive(eng, w, w.t1 - tw, feed, clock, slots=slots, closed=closed,
          waiting=waiting)
    if trace:
        t_tr = clock()
        before = dict(tokens=w.tokens, ticks=eng.ticks,
                      tick_events=w.tick_events,
                      prompt_tokens=w.prompt_tokens, lags=len(w.lags))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # its overhead would slow the host
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with _span("bench.window"):
            drive(eng, w, w.t1, feed, clock, slots=slots, closed=closed,
                  waiting=waiting)
    t_end = clock()
    if trace:
        jax.profiler.stop_trace()
        traced = dict(tokens=w.tokens - before["tokens"],
                      ticks=eng.ticks - before["ticks"],
                      tick_events=w.tick_events - before["tick_events"],
                      prompt_tokens=w.prompt_tokens - before["prompt_tokens"],
                      lags=list(w.lags[before["lags"]:]))
    if eng.tick_traces != tt0 or eng.prefill_traces != pt0:
        raise RunFailed(
            f"the window compiled: tick traces {tt0} -> {eng.tick_traces}, "
            f"prefill traces {pt0} -> {eng.prefill_traces}")
    window_s = t_end - w.t0

    # end-to-end metrics, from the window's own clock
    due_in = [t for t in w.track.values() if w.t0 <= t.due < w.t1]

    def ttft(ts):
        return [((t.first if t.first is not None and t.first <= t_end
                  else t_end) - t.due) for t in ts]

    if trace:
        # the requests due before the traced part, for the per-layer view
        traced["ttft"] = ttft([t for t in due_in if t.due < t_tr])
    e2e = {
        "setup_s": setup_s,
        "decode_tok_s": w.tokens / window_s,
        "itl_p95_ms": 1e3 * nearest_rank(w.itl, 0.95) if w.itl else None,
    }

    return {"w": w, "e2e": e2e, "traced": traced, "feed": feed,
            "setup_s": setup_s, "window_s": window_s, "due": len(due_in),
            "ttft": ttft(due_in), "t_end": t_end, "waiting": waiting,
            "closed": closed}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             control: bool = False, keep_trace: Optional[str] = None,
             trace_seconds: float = TRACE_SECONDS) -> dict:
    """One run of cell `name`.  Returns the result object; raises RunFailed
    when there is no result to give."""
    bench = SPEC.benchmark()
    cell = SPEC.workload(bench, name)
    cfg = SPEC.config(cell["config"])
    tp = SPEC.traffic(cell["traffic"])
    lim = SPEC.limits(name)
    chips = int(cell["chips"])
    devs = _device_check(chips, require_chip)
    from bench import peaks as PK
    peak = PK.peaks(devs[0].device_kind) if require_chip else None

    dep = cfg["deployment"]
    slots = int(dep["slots_per_chip"]) * chips
    mdl = SPEC.model(cfg["model"])
    t_dev = time.perf_counter()
    eng = mdl.build(cfg, seed, slots=slots, max_context=TR.longest(tp),
                    prefill_chunk=int(dep["prefill_chunk"]),
                    mesh_spec=dep.get("mesh"))
    t_built = time.perf_counter()
    eng.warm()
    t_warm = time.perf_counter()
    log(f"[{name}] set-up: devices at {t_dev - t_start:.2f}s, weights, "
        f"runtime and engine {t_built - t_dev:.2f}s, warm "
        f"{t_warm - t_built:.2f}s")

    clock = time.perf_counter
    win = serve_window(eng, cfg, tp, seed, seconds, slots, trace=trace,
                       trace_seconds=trace_seconds, t_start=t_start)
    w, e2e, traced = win["w"], win["e2e"], win["traced"]
    log(f"[{name}] window {win['window_s']:.3f}s: {w.tokens} tokens, "
        f"{w.steps} steps, {win['due']} requests due, {len(w.itl)} gaps; "
        f"set-up {win['setup_s']:.2f}s")
    if win["ttft"]:
        log(f"[{name}] ttft ms: p50 {1e3 * nearest_rank(win['ttft'], 0.5):.1f}"
            f" p95 {1e3 * nearest_rank(win['ttft'], 0.95):.1f}"
            f" max {1e3 * max(win['ttft']):.1f}")

    memory_peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        memory_peak = max(memory_peak, int(stats.get("peak_bytes_in_use", 0)))

    # the numbers that decide `correct`, once the window has closed.  An
    # open loop may leave the slots empty at the close: serve on (not
    # measured) until half of them hold a request (for at most 5 s), or at
    # least one does (for at most 10 s more)
    for want, secs in ((max(1, slots // 2), 5.0), (1, 10.0)):
        until = clock() + secs
        while eng.stats()["active"] < want and clock() < until:
            drive(eng, w, clock() + 0.002, win["feed"], clock, slots=slots,
                  closed=win["closed"], waiting=win["waiting"])
    state = mdl.read_state(eng)
    slot_rows = []
    for rid, tr in w.track.items():
        if tr.done:
            continue
        c = eng.cancel(rid)
        if c is not None and c.slot >= 0:
            slot_rows.append((tr.req.prompt, list(c.tokens), c.slot))
    greedy = [tr for tr in w.track.values()
              if tr.done and tr.req.greedy and tr.tokens]
    greedy.sort(key=lambda tr: -len(tr.tokens))
    rng = np.random.default_rng([int(seed), 7])
    chosen = greedy[:1]
    rest = [greedy[i] for i in rng.permutation(len(greedy))[:len(greedy)]
            if i > 0]
    for tr in rest:
        if (len(chosen) >= GREEDY_ROWS
                or sum(len(c.tokens) for c in chosen) >= GREEDY_TOKENS):
            break
        chosen.append(tr)
    greedy_rows = [(tr.req.prompt, tr.tokens) for tr in chosen]
    attempted = sum(1 for t in w.track.values() if w.t0 <= t.submitted)
    del eng
    gc.collect()
    t_ref = clock()
    res = mdl.check(cfg, seed, greedy_rows=greedy_rows, slot_rows=slot_rows,
                    state=state, t_max=TR.longest(tp), n_greedy=GREEDY_ROWS,
                    control=control)
    log(f"[{name}] reference: {clock() - t_ref:.2f}s over "
        f"{res['greedy_rows']} greedy requests ({res['greedy_tokens']} "
        f"tokens, {res['greedy_tokens_agree']} equal to the reference's "
        f"argmax) and {res['slot_rows']} slots")
    checks = {k: {"value": res[k], "limit": lim[k]["limit"]}
              for k in ("greedy_gap", "state_err")}
    correct = (res["greedy_rows"] > 0 and res["slot_rows"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    result = {"correct": bool(correct), "attempted": attempted, "failed": 0}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    e2e_names = [m["name"] for m in bench["end_to_end"]
                 if SPEC.reports(m, name, ())]
    if not trace:
        metrics = {}
        for m in bench["end_to_end"]:
            if m["name"] not in e2e_names:
                continue
            v = e2e[m["name"]]
            if v is None:
                raise RunFailed(f"{m['name']}: nothing to measure")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
    else:
        from bench import trace as TRC
        from bench import work as WK
        path = _xplane(TRACE_DIR)
        if keep_trace:
            shutil.copy(path, keep_trace)
        summ = TRC.summarize(path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = Ctx(cfg=cfg, chips=chips, slots=slots, peak=peak, work=WK,
                  trace=summ, host=traced)
        metrics = {}
        for m in bench["per_layer"]:
            if not SPEC.reports(m, name, e2e_names):
                continue
            v = SPEC.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = summ.busy_s
        device["window_s"] = summ.window_s
        result["breakdown"] = {"device_ops": summ.top_ops(),
                               "idle_gaps": summ.top_gaps()}
    result["device"] = device
    if control:
        result["control"] = res["control"]
    result["readings"] = {k: res[k] for k in (
        "greedy_rows", "greedy_tokens", "greedy_tokens_agree", "slot_rows",
        "logit_scale", "state_err_max")}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']:.6e} limit {c['limit']:.6e}")
    log(f"correct: {str(correct).lower()}")
    return result


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader gets."""
    cfg: dict
    chips: int
    slots: int
    peak: Optional[dict]
    work: object
    trace: object          # bench.trace.Summary of the traced window
    host: dict             # the harness's counts over the traced window


def _xplane(d) -> str:
    import glob
    found = glob.glob(os.path.join(str(d), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RunFailed(f"expected one trace under {d}, found {len(found)}")
    return found[0]


def preroll(eng, w, pre, feed, clock, *, slots, closed, waiting):
    """Submit the staggered pre-roll requests, one per slot, and (a closed
    loop) keep the queue full behind them, until every one has finished:
    every slot has then retired once."""
    left = set()
    for r in pre:
        rid = _submit(eng, r)
        now = clock()
        w.track[rid] = Track(req=r, due=now, submitted=now)
        w.outstanding += 1
        left.add(rid)
    deadline = clock() + 600.0
    while left:
        if clock() > deadline:
            raise RunFailed("the pre-roll did not finish in 600 s")
        drive(eng, w, clock() + 0.05, feed, clock, slots=slots,
              closed=closed, waiting=waiting, on_done=lambda c: left.discard(
                  c.rid))


def sweep(name: str, seed: int, seconds: float, rates, *, t_start: float,
          require_chip: bool = True) -> list:
    """Offer an open-loop cell's mix at each of `rates` (requests/s) to one
    engine, a window each, and say for each whether the backlog grew: the
    knee is the highest rate at which it does not."""
    bench = SPEC.benchmark()
    cell = SPEC.workload(bench, name)
    cfg = SPEC.config(cell["config"])
    tp = SPEC.traffic(cell["traffic"])
    chips = int(cell["chips"])
    _device_check(chips, require_chip)
    dep = cfg["deployment"]
    slots = int(dep["slots_per_chip"]) * chips
    eng = SPEC.model(cfg["model"]).build(
        cfg, seed, slots=slots, max_context=TR.longest(tp),
        prefill_chunk=int(dep["prefill_chunk"]), mesh_spec=dep.get("mesh"))
    eng.warm()
    rows = []
    for rate in rates:
        win = serve_window(eng, cfg, dict(tp, rate_per_s=float(rate)), seed,
                           seconds, slots, trace=False, trace_seconds=0.0,
                           t_start=t_start)
        w = win["w"]
        due = sorted((t for t in w.track.values()
                      if w.t0 <= t.due < w.t1), key=lambda t: t.due)
        third = max(1, len(due) // 3)
        first = [x for x in win["ttft"][:third]]
        last = [x for x in win["ttft"][-third:]]
        row = {"rate": rate, "due": len(due),
               "ttft_p50_ms": 1e3 * nearest_rank(win["ttft"], 0.5),
               "ttft_p95_ms": 1e3 * nearest_rank(win["ttft"], 0.95),
               "ttft_p50_first_third_ms": 1e3 * nearest_rank(first, 0.5),
               "ttft_p50_last_third_ms": 1e3 * nearest_rank(last, 0.5),
               "unstarted_at_close": sum(1 for t in due if t.first is None
                                         or t.first > win["t_end"]),
               "queued_at_close": eng.stats()["queued"],
               "tokens_s": w.tokens / win["window_s"]}
        rows.append(row)
        log(f"[sweep {name}] {row}")
        for rid, tr in w.track.items():
            if not tr.done:
                eng.cancel(rid)
    return rows
