"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read: the device's busy time, time per program and per operation, and the
idle gaps labelled by what the host was doing.

Read with `jax.profiler.ProfileData` alone.  What it relies on, as a TPU
trace lays it out:

  * each chip is a plane named `/device:TPU:<n>`; its `XLA Ops` line holds
    one event per operation run (name `%<op>.<k> = <hlo text>`, a Pallas
    kernel under the `name=` it was given), its `XLA Modules` line one per
    program run (`jit_<function>(<fingerprint>)`);
  * the host plane `/host:CPU` has a line per thread; the harness's thread
    holds its own spans (`jax.profiler.TraceAnnotation`, names `bench.*`)
    and the runtime's (`PjitFunction(tick)`, `np.asarray(jax.Array)`, ...),
    on the same clock as the devices.

The window is the harness's `bench.window` span.  Busy time is the union
of the operations' intervals inside it, per chip, averaged over the chips.
An idle gap is a stretch of the window in which no operation runs on the
first chip; it is labelled with the innermost `bench.*` span and the
innermost other event of that thread that cover its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
_OP_RE = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=.*)?$", re.S)
_MODULE_RE = re.compile(r"^(.*?)(?:\(\d+\))?$")
CONTAINERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """`%ternary_packed_gemv.6 = f32[8,4096] custom-call(...)` ->
    `ternary_packed_gemv`."""
    m = _OP_RE.match(raw.strip())
    return m.group(1) if m else raw


def module_name(raw: str) -> str:
    """`jit_tick(7267081259785077133)` -> `jit_tick`."""
    return _MODULE_RE.match(raw.strip()).group(1)


@dataclasses.dataclass
class Summary:
    window_s: float
    n_devices: int
    busy_s: float                      # mean over chips
    ops: Dict[str, List[float]]        # name -> [seconds, calls], all chips
    modules: Dict[str, List[float]]    # name -> [seconds, calls], all chips
    gaps: Dict[str, List[float]]       # label -> [seconds, count, longest]

    def per_call_s(self, table: str, match) -> Tuple[float, int]:
        """Mean device seconds per call, and the call count, of the
        entries of `ops` or `modules` whose name satisfies `match`."""
        rows = [v for k, v in getattr(self, table).items() if match(k)]
        calls = sum(v[1] for v in rows)
        if not calls:
            return 0.0, 0
        return sum(v[0] for v in rows) / calls, int(calls)

    def top_ops(self, n: int = 10) -> List[list]:
        rows = [(k, v[0] / self.n_devices) for k, v in self.ops.items()
                if not k.startswith(CONTAINERS)]
        rows.sort(key=lambda kv: -kv[1])
        return [[k, s] for k, s in rows[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        rows = sorted(self.gaps.items(), key=lambda kv: -kv[1][0])
        return [[k, v[0]] for k, v in rows[:n]]


def _events(line):
    for e in line.events:
        s = float(e.start_ns)
        yield s, s + float(e.duration_ns), e.name


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class _Cover:
    """Innermost event covering a point, for properly nested events."""

    def __init__(self, events):
        self.ev = sorted(events)
        self.starts = [e[0] for e in self.ev]

    def at(self, t: float, limit: int = 20000):
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - limit, -1), -1):
            s, e, name = self.ev[j]
            if e >= t:
                return name
        return None


def summarize(path: str) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            host = list(plane.lines)
    spans, frames = [], []
    for ln in host:
        evs = list(_events(ln))
        if any(name == WINDOW_SPAN for _, _, name in evs):
            for s, e, name in evs:
                (spans if name.startswith("bench.") else frames).append(
                    (s, e, name))
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span on the host")
    w0, w1 = win[0]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")

    ops: Dict[str, List[float]] = {}
    modules: Dict[str, List[float]] = {}
    busy = []
    first_busy = None
    for k, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        intervals = []
        for ln in plane.lines:
            if ln.name not in ("XLA Ops", "XLA Modules"):
                continue
            table, name_of = ((ops, op_name) if ln.name == "XLA Ops"
                              else (modules, module_name))
            for s, e, raw in _events(ln):
                if s < w0 or s >= w1:
                    continue
                row = table.setdefault(name_of(raw), [0.0, 0])
                row[0] += (e - s) * 1e-9
                row[1] += 1
                if ln.name == "XLA Ops":
                    intervals.append((s, min(e, w1)))
        merged = _merge(intervals)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if k == 0:
            first_busy = merged

    span_cover = _Cover([x for x in spans if x[2] != WINDOW_SPAN])
    frame_cover = _Cover(frames)
    gaps: Dict[str, List[float]] = {}
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        label = (span_cover.at(mid) or WINDOW_SPAN) + " > " + (
            frame_cover.at(mid) or "-")
        row = gaps.setdefault(label, [0.0, 0, 0.0])
        row[0] += (b - a) * 1e-9
        row[1] += 1
        row[2] = max(row[2], (b - a) * 1e-9)
    return Summary(window_s=(w1 - w0) * 1e-9, n_devices=len(devices),
                   busy_s=sum(busy) / len(busy), ops=ops, modules=modules,
                   gaps=gaps)
