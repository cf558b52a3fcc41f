"""Front door + resumable step API (DESIGN.md §10).

The engine grew `submit()`/`step()`/`cancel(rid)` so an event loop can
drive ticks while requests arrive and die asynchronously.  The contract
stays the PR 3 one: scheduling is INVISIBLE.  Driving the scheduler one
step at a time, over HTTP, with clients hanging up mid-stream, must leave
every SURVIVING stream byte-identical to the sequential `drive_session`
oracle — and must never trace a new tick (cancellation reuses the compiled
scrub; `tick_traces`/`spec_traces` stay 1 for the engine's life).

Engines are cached per (family, slots, chunk) and reused across tests, so
the suite re-proves the compile-once invariant under submit/cancel churn,
not just under batch replay.
"""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import bnlstm as BL
from repro.core.quantize import QuantSpec
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.serve.frontdoor import FrontDoor, _get_json, _post_stream
from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                   drive_session, speculative_draft)

CTX = 48

_RUNTIMES: dict = {}
_ENGINES: dict = {}


def _runtime(family):
    if family not in _RUNTIMES:
        if family.startswith("lstm"):
            packed = family == "lstm-packed"
            spec = (QuantSpec(mode="ternary", norm="batch") if packed
                    else QuantSpec(mode="none"))
            cfg = BL.RNNConfig(vocab=24, d_hidden=48, n_layers=2,
                               cell="lstm", quant=spec)
            var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
            params = var["params"]
            if packed:
                params = BL.export_packed_rnn(params, cfg)
            rt = RNNRuntime(cfg, {"params": params, "state": var["state"]})
            _RUNTIMES[family] = (rt, cfg.vocab, None)
        else:
            cfg = get_config("qwen3-0.6b").reduced()
            params = T.model_init(jax.random.PRNGKey(0), cfg)
            rt = TransformerRuntime(cfg, params)
            _RUNTIMES[family] = (rt, cfg.vocab, CTX)
    return _RUNTIMES[family]


def _engine(family, slots, chunk):
    key = (family, slots, chunk)
    if key not in _ENGINES:
        rt, vocab, _ = _runtime(family)
        _ENGINES[key] = ServeEngine(rt, vocab, slots=slots, max_context=CTX,
                                    prefill_chunk=chunk)
    return _ENGINES[key]


def _expected(family, req):
    rt, vocab, ctx = _runtime(family)
    out, _ = drive_session(
        rt, jnp.asarray(req.prompt)[None], vocab, gen=req.max_tokens,
        temperature=req.temperature, top_k=req.top_k, seed=req.seed,
        context=ctx)
    return out[0].tolist()


def _reqs(vocab, n, *, seed=0, max_prompt=12, max_gen=10):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, vocab,
                                        size=int(rng.integers(2, max_prompt))),
                    max_tokens=int(rng.integers(2, max_gen)),
                    temperature=0.8, top_k=5, seed=500 + i)
            for i in range(n)]


def _drain(eng):
    """Drive step() to empty, collecting per-rid streams and completions."""
    streams: dict = {}
    comps = []
    while eng.has_work():
        events, cs = eng.step()
        for rid, toks in events:
            streams.setdefault(rid, []).extend(toks)
        comps.extend(cs)
    return streams, comps


# --- the resumable step API --------------------------------------------------


@pytest.mark.parametrize("family", ["lstm-packed", "lstm-fp", "qwen3"])
def test_step_api_streams_match_drive_session(family):
    """submit-all + step-to-empty produces the exact per-request streams the
    batch run() (and therefore the sequential oracle) produces."""
    rt, vocab, _ = _runtime(family)
    eng = _engine(family, 2, 4)
    reqs = _reqs(vocab, 4, seed=11)
    rids = [eng.submit(dataclasses.replace(r)) for r in reqs]
    streams, comps = _drain(eng)
    assert sorted(streams) == sorted(rids) and len(comps) == len(reqs)
    for rid, req in zip(rids, reqs):
        assert streams[rid] == _expected(family, req), \
            f"step-API stream for rid {rid} diverged from the oracle"
    for c in comps:
        assert c.tokens == streams[c.rid]  # events and completions agree
    assert eng.tick_traces == 1


def test_run_is_the_step_loop():
    """The batch driver is a THIN wrapper: same engine, same streams."""
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 2, 4)
    reqs = _reqs(vocab, 5, seed=23)
    comps, m = eng.run([dataclasses.replace(r, rid=100 + i)
                        for i, r in enumerate(reqs)], realtime=False)
    by_rid = {c.rid: c.tokens for c in comps}
    for i, req in enumerate(reqs):
        assert by_rid[100 + i] == _expected("lstm-packed", req)
    assert m["tick_traces"] == 1 and eng.tick_traces == 1


# --- cancellation ------------------------------------------------------------


def test_cancel_mid_prefill():
    """Cancelling a request whose prompt is still chunk-prefilling frees the
    slot through the shape-aware scrub: the survivor's stream is untouched
    and the next occupant of that slot starts from a clean row."""
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 2, 2)
    long = Request(prompt=np.arange(12) % vocab, max_tokens=30,
                   temperature=0.0, seed=1)       # 6 chunks of 2
    short = _reqs(vocab, 1, seed=31)[0]
    rid_l = eng.submit(dataclasses.replace(long))
    rid_s = eng.submit(dataclasses.replace(short))
    eng.step()  # admits both, runs ONE chunk of the long prompt
    assert eng._active[0] is not None and eng._active[0].chunks
    traces = (eng.tick_traces, eng.prefill_traces)
    comp = eng.cancel(rid_l)
    assert (eng.tick_traces, eng.prefill_traces) == traces, \
        "cancellation must not trace anything new"
    assert comp.finished == "cancelled" and comp.tokens == []
    assert eng._active[0] is None and 0 not in eng._prefill_q
    streams, comps = _drain(eng)
    assert [c.rid for c in comps] == [rid_s]
    assert streams[rid_s] == _expected("lstm-packed", short)
    # the freed slot is immediately reusable and reads like fresh
    readmit = Request(prompt=np.asarray(long.prompt), max_tokens=6,
                      temperature=0.0, seed=1)
    rid2 = eng.submit(dataclasses.replace(readmit))
    streams2, comps2 = _drain(eng)
    assert comps2[0].slot in (0, 1)
    assert streams2[rid2] == _expected("lstm-packed", readmit)
    assert eng.tick_traces == 1


def test_cancel_queued_request_never_touches_a_slot():
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 1, 4)
    a, b = _reqs(vocab, 2, seed=41)
    rid_a = eng.submit(dataclasses.replace(a))
    rid_b = eng.submit(dataclasses.replace(b))   # queued: one slot
    eng.step()
    comp = eng.cancel(rid_b)
    assert comp is not None and comp.finished == "cancelled"
    assert comp.slot == -1 and comp.tokens == []
    streams, comps = _drain(eng)
    assert [c.rid for c in comps] == [rid_a]
    assert streams[rid_a] == _expected("lstm-packed", a)
    assert eng.cancel(rid_b) is None  # already gone: idempotent


def test_disconnect_then_readmit_same_slot():
    """The front-door disconnect path: cancel a DECODING request, then the
    next request lands in the same slot and must stream exactly the oracle
    — nothing of the dead request leaks through the scrub."""
    rt, vocab, _ = _runtime("lstm-fp")
    eng = _engine("lstm-fp", 1, 4)
    a = Request(prompt=np.arange(5) % vocab, max_tokens=30, temperature=0.8,
                top_k=5, seed=7)
    b = _reqs(vocab, 1, seed=51)[0]
    rid_a = eng.submit(dataclasses.replace(a))
    got_a = []
    for _ in range(6):  # prefill (2 chunks) + a few decode ticks
        events, _ = eng.step()
        for rid, toks in events:
            got_a.extend(toks)
    assert eng._live_host[0] and len(got_a) >= 2
    comp_a = eng.cancel(rid_a)
    # the cancel settles the tick in flight: its token is in the
    # completion, and reaches the stream as an event of the next step()
    assert comp_a.finished == "cancelled"
    assert comp_a.tokens == got_a + [comp_a.tokens[-1]]
    assert comp_a.tokens == _expected("lstm-fp", a)[:len(comp_a.tokens)], \
        "the partial stream up to the hangup is still oracle-exact"
    rid_b = eng.submit(dataclasses.replace(b))
    streams, comps = _drain(eng)
    assert got_a + streams[rid_a] == comp_a.tokens
    assert comps[0].rid == rid_b and comps[0].slot == 0  # SAME slot
    assert streams[rid_b] == _expected("lstm-fp", b)
    assert eng.tick_traces == 1


def test_cancel_between_spec_rounds():
    """Speculative engines cancel at the only boundary that exists — between
    one draft-verify-accept round and the next.  Killing a slot mid-flight
    must leave the survivors' streams byte-identical to the oracle (the
    draft pool's rollback state for the dead slot is scrubbed with it) and
    trace nothing new."""
    rt, vocab, _ = _runtime("lstm-fp")
    key = ("spec", 2, 4, 3)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            rt, vocab, slots=2, max_context=CTX, prefill_chunk=4,
            draft=speculative_draft(rt, mode="ternary"), spec_k=3)
    eng = _ENGINES[key]
    a = Request(prompt=np.arange(4) % vocab, max_tokens=24, temperature=0.0,
                seed=3)
    b = Request(prompt=(np.arange(6) * 5) % vocab, max_tokens=10,
                temperature=0.0, seed=4)
    rid_a = eng.submit(dataclasses.replace(a))
    rid_b = eng.submit(dataclasses.replace(b))
    got = {rid_a: [], rid_b: []}
    while not (eng._live_host[0] and eng._live_host[1]):
        for rid, toks in eng.step()[0]:
            got[rid].extend(toks)
    for rid, toks in eng.step()[0]:  # >= one spec round, both slots live
        got[rid].extend(toks)
    traces = eng.spec_traces
    comp_a = eng.cancel(rid_a)
    assert eng.spec_traces == traces, \
        "spec cancel churn must not retrace the round"
    assert comp_a.finished == "cancelled" and comp_a.tokens == got[rid_a]
    assert comp_a.tokens == _expected("lstm-fp", a)[:len(comp_a.tokens)]
    streams, comps = _drain(eng)
    assert [c.rid for c in comps] == [rid_b]
    assert got[rid_b] + streams.get(rid_b, []) == comps[0].tokens
    assert comps[0].tokens == _expected("lstm-fp", b)
    assert eng.spec_traces == 1


# --- priority / SLO admission ------------------------------------------------


def test_priority_orders_admission_not_preemption():
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 1, 4)
    reqs = [Request(prompt=np.arange(3) % vocab, max_tokens=3,
                    temperature=0.0, seed=60 + i, priority=p, slo=s)
            for i, (p, s) in enumerate([(5, "batch"), (0, "realtime"),
                                        (2, "standard")])]
    rids = [eng.submit(dataclasses.replace(r)) for r in reqs]
    streams, comps = _drain(eng)
    # one slot: completion order IS admission order -> priority order
    assert [c.rid for c in comps] == [rids[1], rids[2], rids[0]]
    assert [c.slo for c in comps] == ["realtime", "standard", "batch"]
    for rid, req in zip(rids, reqs):
        assert streams[rid] == _expected("lstm-packed", req), \
            "admission order must never change a stream's bytes"


def test_ttft_reported_per_slo_class():
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 2, 4)
    reqs = _reqs(vocab, 4, seed=71)
    reqs = [dataclasses.replace(r, slo="interactive" if i % 2 else "batch",
                                priority=0 if i % 2 else 1)
            for i, r in enumerate(reqs)]
    _, m = eng.run(reqs, realtime=False)
    cls = m["ttft_by_class"]
    assert set(cls) == {"interactive", "batch"}
    for v in cls.values():
        assert v["n"] == 2 and 0 <= v["p50_s"] <= v["p95_s"]


# --- the HTTP/SSE layer ------------------------------------------------------


def _sse_roundtrip(eng, payloads, hangup_after=None):
    """Serve `eng` on an ephemeral port, POST each payload, return the
    streamed tokens (+ done events).  `hangup_after` maps payload index ->
    close-after-N-events (the disconnect path)."""
    hangup_after = hangup_after or {}

    async def go():
        fd = FrontDoor(eng, port=0)
        await fd.start()
        try:
            outs = []
            for i, p in enumerate(payloads):
                outs.append(await _post_stream(fd.host, fd.port, p,
                                               hangup_after=hangup_after.get(i)))
                await asyncio.sleep(0.05)  # let a hangup cancel before next
            stats = await _get_json(fd.host, fd.port, "/v1/stats")
            return outs, stats
        finally:
            await fd.close()

    return asyncio.run(go())


@pytest.mark.parametrize("family", ["lstm-packed", "lstm-fp", "qwen3"])
def test_sse_streams_are_oracle_exact(family):
    """The acceptance bar: token sequences streamed over HTTP/SSE are
    byte-identical to drive_session for the same seed/params, with the
    tick compiled exactly once under submit/cancel churn."""
    rt, vocab, _ = _runtime(family)
    eng = _engine(family, 2, 4)
    reqs = _reqs(vocab, 3, seed=83)
    payloads = [{"prompt": np.asarray(r.prompt).tolist(),
                 "max_tokens": r.max_tokens, "temperature": r.temperature,
                 "top_k": r.top_k, "seed": r.seed} for r in reqs]
    # payload 1 hangs up after its first token event (mid-stream cancel);
    # bump its gen budget so there IS a mid-stream to hang up in
    payloads[1]["max_tokens"] = 20
    outs, stats = _sse_roundtrip(eng, payloads, hangup_after={1: 1})
    for i in (0, 2):
        toks, done = outs[i]
        assert done is not None and done["finished"] in ("eos", "length")
        assert toks == _expected(family, reqs[i]), \
            f"SSE stream {i} diverged from the sequential oracle"
    # the cancelled stream's prefix is oracle-exact too
    cut, _ = outs[1]
    exp1 = _expected(family, dataclasses.replace(reqs[1], max_tokens=20))
    assert cut == exp1[:len(cut)]
    assert stats["active"] == 0 and stats["queued"] == 0
    assert stats["tick_traces"] == 1


def test_http_bad_requests_are_rejected():
    eng = _engine("lstm-packed", 2, 4)

    async def go():
        fd = FrontDoor(eng, port=0)
        await fd.start()
        try:
            r1, w1 = await asyncio.open_connection(fd.host, fd.port)
            body = b'{"prompt": [1, 2], "max_tokens": 0}'  # invalid budget
            w1.write(b"POST /v1/generate HTTP/1.1\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
            await w1.drain()
            resp = await r1.read()
            w1.close()
            nf = await _get_json(fd.host, fd.port, "/nope")
            return resp, nf
        finally:
            await fd.close()

    resp, nf = asyncio.run(go())
    assert b"400 Bad Request" in resp and b"max_tokens" in resp
    assert "error" in nf
    assert not eng.has_work()


def test_stats_report_per_shard_occupancy_and_queue_depth():
    """`stats()` / `/v1/stats` carry the mesh-serving observability fields
    on EVERY engine (DESIGN.md §12): a `shards` list (one row per data
    shard; a single-device engine is one shard spanning all slots) whose
    `active` sums to the engine's, plus `queue_depth` for the admission
    queue — so dashboards need no schema fork when --mesh lands."""
    rt, vocab, _ = _runtime("lstm-packed")
    eng = _engine("lstm-packed", 2, 4)
    for r in _reqs(vocab, 3, seed=91):   # 3 requests, 2 slots -> 1 queued
        eng.submit(r)
    eng.step()
    mid = eng.stats()
    assert mid["queue_depth"] == mid["queued"] == 1
    assert [s["shard"] for s in mid["shards"]] == [0]
    assert mid["shards"][0]["slots"] == 2
    assert sum(s["active"] for s in mid["shards"]) == mid["active"] == 2
    assert mid["shards"][0]["occupancy"] == 1.0
    assert "mesh" not in mid             # meshless engine: no mesh block
    _drain(eng)

    stats = _sse_roundtrip(eng, [])[1]   # same fields over HTTP
    assert stats["queue_depth"] == 0
    assert stats["shards"][0]["active"] == 0
    assert stats["shards"][0]["occupancy"] == 0.0
