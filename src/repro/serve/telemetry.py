"""Host spans and counters of the serving engine (PERF.md section 3).

The recorder is process-wide, not per engine, so what it kept outlives the
engine that made it: the benchmark's per-layer readers run after the engine
is freed.  It records only while a JAX profiler session captures
(`jax.profiler.TraceAnnotation.is_enabled()`); the session is the switch,
and there is no other.  `ServeEngine.step()` asks `active()` once per call:
with nothing capturing it opens no span and creates no annotation.

Each span is a `jax.profiler.TraceAnnotation`, so it lands in the profile
on the device trace's clock, plus an in-memory record kept as totals per
name: the count, the summed duration and the summed duration of the spans
opened directly inside it (its self time is the difference).  Queue waits
(submit to admit) are kept per admission, the last `MAX_WAITS` of them.
The record is cleared when capture turns on, so it holds one session.

`snapshot()` is the read side: plain dicts and lists, read by the
benchmark's `bench/metrics/*.py`.

Separately, and always on, a `jax.monitoring` listener counts the programs
JAX builds in the process (backend compiles and persistent-cache loads,
both inside the `BACKEND_COMPILE` event), read by `ServeEngine.stats()`."""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Deque, Dict, List

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
MAX_WAITS = 1 << 16
_OFF = contextlib.nullcontext()


class Recorder:
    def __init__(self):
        self.on = False           # the last active() saw a session
        self.compiles = 0         # programs built in the process, lifetime
        self.clear()

    def clear(self):
        # name -> [count, total_s, child_s]
        self.spans: Dict[str, List[float]] = {}
        self.queue: Deque[float] = collections.deque(maxlen=MAX_WAITS)
        self.stack: List[str] = []

    def active(self) -> bool:
        on = jax.profiler.TraceAnnotation.is_enabled()
        if on and not self.on:
            self.clear()
        self.on = on
        return on

    @contextlib.contextmanager
    def span(self, name: str):
        parent = self.stack[-1] if self.stack else None
        self.stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.stack.pop()
            row = self.spans.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += dt
            if parent is not None:
                self.spans.setdefault(parent, [0, 0.0, 0.0])[2] += dt

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.compiles += 1


REC = Recorder()
jax.monitoring.register_event_duration_secs_listener(REC.on_duration)


def active() -> bool:
    """Is a profiler session capturing?  Clears the record when capture
    has turned on since the last call.  Call once per engine step."""
    return REC.active()


def span(name: str):
    """A recorded span (call only after `active()` said True)."""
    return REC.span(name)


def no_span(name: str):
    """The stand-in for `span` when nothing captures: a shared no-op."""
    return _OFF


def queue_wait(wait_s: float) -> None:
    """One admission's wait from submit to admit (while recording)."""
    REC.queue.append(float(wait_s))


def compiles() -> int:
    """Programs JAX built in this process so far."""
    return REC.compiles


def snapshot() -> dict:
    """The current session's record: `spans` name -> {"n", "total_s",
    "self_s"}; `queue_s` the admissions' waits, seconds."""
    return {
        "spans": {k: {"n": int(n), "total_s": s, "self_s": s - c}
                  for k, (n, s, c) in REC.spans.items()},
        "queue_s": list(REC.queue),
    }
