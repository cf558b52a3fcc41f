"""The engine's host spans and counters (serve/telemetry.py): recorded only
while a JAX profiler session captures, counted the way the engine's own
counters count, and never a change to the tokens."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.core import bnlstm as BL
from repro.core.quantize import QuantSpec
from repro.serve import telemetry
from repro.serve.engine import Request, ServeEngine
from repro.serve.recurrent import RNNRuntime, speculative_draft

VOCAB = 24
MODES = ("plain", "spec")


def _runtime(packed: bool):
    cfg = BL.RNNConfig(vocab=VOCAB, d_hidden=48, n_layers=1, cell="lstm",
                       quant=QuantSpec(mode="ternary" if packed else "none",
                                       norm="batch"))
    var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
    params = var["params"]
    if packed:
        params = BL.export_packed_rnn(params, cfg)
    return RNNRuntime(cfg, {"params": params, "state": var["state"]})


def _engine(mode: str) -> ServeEngine:
    if mode == "plain":
        return ServeEngine(_runtime(True), VOCAB, slots=2, max_context=64,
                           prefill_chunk=4)
    rt = _runtime(False)
    return ServeEngine(rt, VOCAB, slots=2, max_context=64, prefill_chunk=4,
                       draft=speculative_draft(rt, mode="ternary"), spec_k=2)


@pytest.fixture(scope="module")
def engines():
    out = {}
    for mode in MODES:
        eng = _engine(mode)
        eng.warm()
        out[mode] = eng
    return out


def _requests(lens, temperature=0.0):
    rng = np.random.default_rng(len(lens))
    return [Request(prompt=rng.integers(0, VOCAB, size=n), max_tokens=6,
                    temperature=temperature, top_k=0, seed=10 + i)
            for i, n in enumerate(lens)]


def _serve(eng, reqs):
    """Submit every request, step until the engine is idle; the tokens of
    each request in submit order."""
    rids = [eng.submit(dataclasses.replace(r)) for r in reqs]
    got = {rid: [] for rid in rids}
    while eng.has_work():
        for rid, toks in eng.step()[0]:
            got[rid].extend(toks)
    return [got[rid] for rid in rids]


def _traced(eng, reqs, path):
    """`_serve` under a profiler session, after one idle step with capture
    off: the record holds one session from capture's turning on, as in a
    benchmark run, whose traced part follows its untraced part."""
    eng.step()
    with jax.profiler.trace(str(path)):
        return _serve(eng, reqs)


class _Counting(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, *a, **kw):
        type(self).made += 1
        super().__init__(*a, **kw)


@pytest.mark.parametrize("mode", MODES)
def test_nothing_recorded_without_a_session(engines, mode, monkeypatch):
    telemetry.REC.clear()
    _Counting.made = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    _serve(engines[mode], _requests([3, 9]))
    snap = telemetry.snapshot()
    assert snap["spans"] == {} and snap["queue_s"] == []
    assert _Counting.made == 0


@pytest.mark.parametrize("mode", MODES)
def test_session_counts_match_the_engine(engines, mode, tmp_path):
    eng = engines[mode]
    reqs = _requests([3, 9, 5, 13])
    chunks = sum(len(eng._chunk_plan(r.prompt.size)) for r in reqs)
    ticks0, compiles0 = eng.ticks, eng.stats()["compiles"]
    _traced(eng, reqs, tmp_path)
    snap = telemetry.snapshot()
    spans = snap["spans"]
    assert spans["serve.tick"]["n"] == eng.ticks - ticks0 > 0
    assert spans["serve.ids_wait"]["n"] == spans["serve.ids_copy"]["n"] \
        == spans["serve.tick"]["n"]
    assert spans["serve.prefill"]["n"] == chunks
    assert len(snap["queue_s"]) == len(reqs)
    assert all(w >= 0 for w in snap["queue_s"])
    for name, row in spans.items():
        assert row["total_s"] > 0 and row["self_s"] >= 0, name
    step = spans["serve.step"]
    assert step["self_s"] < step["total_s"]
    # a warmed engine builds nothing while it is traced
    assert eng.stats()["compiles"] == compiles0


@pytest.mark.parametrize("mode", MODES)
def test_ticks_ahead_counts_plain_ticks_dispatched_before_a_read(
        engines, mode, tmp_path):
    """`serve.ticks_ahead` counts ticks dispatched while an earlier tick's
    ids were unread: on the plain path, every tick of a stretch of steady
    decoding but its first; a speculative round is read in its own step."""
    eng = engines[mode]
    _traced(eng, _requests([3, 5]), tmp_path)
    snap = telemetry.snapshot()
    ahead = snap["counts"].get("serve.ticks_ahead", 0)
    ticks = snap["spans"]["serve.tick"]["n"]
    if mode == "spec":
        assert ahead == 0
    else:
        assert 0 < ahead < ticks


def test_a_new_session_clears_the_last(engines, tmp_path):
    eng = engines["plain"]
    _traced(eng, _requests([3, 9]), tmp_path / "a")
    first = telemetry.snapshot()["spans"]["serve.prefill"]["n"]
    _serve(eng, _requests([2]))    # capture off in between
    with jax.profiler.trace(str(tmp_path / "b")):
        _serve(eng, _requests([5]))
    snap = telemetry.snapshot()
    assert first == 4 and snap["spans"]["serve.prefill"]["n"] == 2
    assert len(snap["queue_s"]) == 1


@pytest.mark.parametrize("mode", MODES)
def test_a_session_changes_no_token(engines, mode, tmp_path):
    eng = engines[mode]
    reqs = _requests([2, 7, 11, 4, 6])
    plain = _serve(eng, reqs)
    traced = _traced(eng, reqs, tmp_path)
    assert traced == plain and all(plain)


def test_warm_builds_and_a_warm_engine_builds_nothing_more():
    eng = _engine("plain")
    c0 = eng.stats()["compiles"]
    eng.warm()
    c1 = eng.stats()["compiles"]
    assert c1 > c0
    # prompt lengths warm() never saw: every chunk and tail bucket
    _serve(eng, _requests([1, 2, 3, 5, 6, 7, 10, 15], temperature=0.8))
    assert eng.stats()["compiles"] == c1


def test_submit_times_a_live_request_from_its_submit(engines):
    """A request with no arrival time of its own is timed from submit(),
    not from the engine's start."""
    eng = engines["plain"]
    _serve(eng, _requests([3]))
    time.sleep(0.2)
    rid = eng.submit(_requests([5])[0])
    comps = []
    while eng.has_work():
        comps += eng.step()[1]
    (c,) = [c for c in comps if c.rid == rid]
    assert 0 <= c.queue_s < 0.2 and 0 <= c.ttft_s < 0.2
    assert c.t_submit <= c.t_admit <= c.t_first <= c.t_done
