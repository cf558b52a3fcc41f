"""The readers of the program's own spans (`source: program_span`): each
reads a positive number after a small engine served under a profiler
session, and nothing (None) with no session or with a program that keeps
no such record."""
import sys

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import spec as SPEC

READERS = ["tick_dispatch_us.decode", "ids_wait_us.decode",
           "ids_copy_us.decode", "step_self_us.decode",
           "prefill_host_us.chat", "queue_wait_ms_mean.chat"]


def test_readers_are_in_the_benchmark():
    listed = {m["name"]: m for m in SPEC.benchmark()["per_layer"]}
    for name in READERS:
        assert listed[name]["source"] == "program_span"


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
    for i in range(8):     # twice the slots, so some requests queue
        eng.submit(Request(prompt=rng.integers(0, cfg["vocab"], size=40 + i),
                           max_tokens=24, temperature=0.0, seed=i))
    while eng.has_work():
        eng.step()


def _ctx(cfg):
    return H.Ctx(cfg=cfg, chips=1, slots=4, peak=None, work=None, trace=None,
                 host={})


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_session(engine, name, tmp_path):
    cfg, eng = engine
    with jax.profiler.trace(str(tmp_path)):
        _serve(cfg, eng)
    v = SPEC.metric_reader(name)(_ctx(cfg))
    assert v is not None and v > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_a_session(engine, name):
    from repro.serve import telemetry

    cfg, eng = engine
    telemetry.REC.clear()
    _serve(cfg, eng)
    assert SPEC.metric_reader(name)(_ctx(cfg)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_the_record(
        engine, name, monkeypatch):
    import repro.serve

    cfg, _ = engine
    monkeypatch.delattr(repro.serve, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.serve.telemetry", None)
    assert SPEC.metric_reader(name)(_ctx(cfg)) is None
