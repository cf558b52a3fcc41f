"""The readers of the program's own counters (`source: program_counter`
over `serve/telemetry.py`): `ahead_share.decode` reads a share after a
small engine served under a profiler session, and nothing (None) with no
session, with a program whose record keeps no counters, or with a program
that keeps no record at all."""
import sys

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import spec as SPEC

NAME = "ahead_share.decode"


def test_reader_is_in_the_benchmark():
    listed = {m["name"]: m for m in SPEC.benchmark()["per_layer"]}
    assert listed[NAME]["source"] == "program_counter"
    assert listed[NAME]["moves"] == "decode_tok_s"


@pytest.fixture(scope="module")
def engine():
    from conftest import shrink_config

    cfg = shrink_config(SPEC.config("char-ptb"))
    eng = SPEC.model(cfg["model"]).build(cfg, 5, slots=4, max_context=160,
                                         prefill_chunk=32, mesh_spec=None)
    eng.warm()
    return cfg, eng


def _serve(cfg, eng):
    from repro.serve.engine import Request

    rng = np.random.default_rng(0)
    for i in range(8):     # twice the slots, so the pool stays full
        eng.submit(Request(prompt=rng.integers(0, cfg["vocab"], size=40 + i),
                           max_tokens=24, temperature=0.0, seed=i))
    while eng.has_work():
        eng.step()


def _ctx(cfg):
    return H.Ctx(cfg=cfg, chips=1, slots=4, peak=None, work=None, trace=None,
                 host={})


def test_reader_reads_a_session(engine, tmp_path):
    cfg, eng = engine
    with jax.profiler.trace(str(tmp_path)):
        _serve(cfg, eng)
    v = SPEC.metric_reader(NAME)(_ctx(cfg))
    assert v is not None and 50.0 < v < 100.0


def test_reader_reads_nothing_without_a_session(engine):
    from repro.serve import telemetry

    cfg, eng = engine
    telemetry.REC.clear()
    _serve(cfg, eng)
    assert SPEC.metric_reader(NAME)(_ctx(cfg)) is None


def test_reader_reads_nothing_from_a_record_without_counters(
        engine, monkeypatch, tmp_path):
    from repro.serve import telemetry

    cfg, eng = engine
    with jax.profiler.trace(str(tmp_path)):
        _serve(cfg, eng)
    snap = telemetry.snapshot()
    assert snap["spans"]["serve.tick"]["n"] > 0
    del snap["counts"]
    monkeypatch.setattr(telemetry, "snapshot", lambda: snap)
    assert SPEC.metric_reader(NAME)(_ctx(cfg)) is None


def test_reader_reads_nothing_from_a_program_without_the_record(
        engine, monkeypatch):
    import repro.serve

    cfg, _ = engine
    monkeypatch.delattr(repro.serve, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    assert SPEC.metric_reader(NAME)(_ctx(cfg)) is None
