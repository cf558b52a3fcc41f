"""Prefix-state cache (DESIGN.md §10): a spliced prefix must be INVISIBLE.

A request whose prompt prefix is served from the cache — one state-row
splice instead of re-prefilling — must stream bytes identical to the same
request cold, which (by the §8 chunked-prefill contract) is identical to
the sequential oracle.  Proven for the paper's LSTM (packed ternary: the
snapshot is two (L, H) rows), for an attention arch (qwen3: narrowed kv
columns, zero-widened at splice) and for the zamba2 hybrid (SSM states
beside narrowed kv).  Plus the cache's own guarantees: LRU
eviction under the byte budget, a poisoned-prefix guard (digest match with
different stored ids is a collision, never a hit), and one-trace splicing.
"""
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
from repro.serve.kvcache import (cache_init, cache_narrow, cache_update,
                                 cache_widen)
from repro.serve.prefixcache import PrefixCache, tree_bytes
from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                   drive_session, speculative_draft)

CTX = 48
_RUNTIMES: dict = {}


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
            arch = "zamba2-7b" if family == "zamba2" else "qwen3-0.6b"
            cfg = get_config(arch).reduced()
            params = T.model_init(jax.random.PRNGKey(0), cfg)
            rt = TransformerRuntime(cfg, params)
            _RUNTIMES[family] = (rt, cfg.vocab, CTX)
    return _RUNTIMES[family]


def _expected(family, req):
    rt, vocab, ctx = _runtime(family)
    out, _ = drive_session(
        rt, jnp.asarray(req.prompt)[None], vocab, gen=req.max_tokens,
        temperature=req.temperature, top_k=req.top_k, seed=req.seed,
        context=ctx)
    return out[0].tolist()


# --- kv narrow/widen ---------------------------------------------------------


def test_cache_narrow_widen_roundtrip():
    sub = cache_init(1, 8, 2, 4, jnp.float32, per_slot=True)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 4))
    sub = cache_update(sub, k, 2 * k)
    nar = cache_narrow(sub, 4)
    assert nar.k.shape == (1, 4, 2, 4) and nar.pos.tolist() == [4]
    wide = cache_widen(nar, sub.k.shape)
    np.testing.assert_array_equal(np.asarray(wide.k[:, :4]),
                                  np.asarray(sub.k[:, :4]))
    assert float(jnp.abs(wide.k[:, 4:]).max()) == 0.0  # zero tail: masked
    assert wide.pos.tolist() == [4]
    assert cache_widen(nar, nar.k.shape) is nar  # already full: no-op


def test_cache_narrow_rejects_ring():
    ring = cache_init(1, 8, 2, 4, jnp.float32, per_slot=True, ring=True)
    with pytest.raises(ValueError):
        cache_narrow(ring, 4)


# --- the cache data structure ------------------------------------------------


def _entry_state(nbytes):
    return np.zeros(nbytes, np.int8)


def test_lru_eviction_under_byte_budget():
    c = PrefixCache(100)
    c.bind(4)
    t = lambda i: np.full(4, i, np.int32)
    assert c.insert(t(1), _entry_state(40))
    assert c.insert(t(2), _entry_state(40))
    assert len(c) == 2 and c.bytes == 80
    c.lookup(np.concatenate([t(1), t(9)]))  # touch 1: now 2 is LRU
    assert c.insert(t(3), _entry_state(40))  # evicts 2, not 1
    s = c.stats()
    assert s["entries"] == 2 and s["bytes"] == 80 and s["evictions"] == 1
    assert c.lookup(np.concatenate([t(1), t(9)]))[0] == 4
    assert c.lookup(np.concatenate([t(2), t(9)]))[0] == 0  # evicted
    assert not c.insert(t(4), _entry_state(101))  # bigger than the budget
    assert c.stats()["entries"] == 2


def test_longest_boundary_prefix_wins_and_last_chunk_never_cached():
    c = PrefixCache(1 << 20)
    c.bind(4)
    p = np.arange(12, dtype=np.int32)
    c.insert(p[:4], _entry_state(8))
    c.insert(p[:8], _entry_state(8))
    assert c.lookup(p)[0] == 8       # longest wins, capped at size-1=11 -> 8
    assert c.lookup(p[:9])[0] == 8
    # a prompt that IS a cached boundary still re-runs its last chunk:
    # the cap is size-1, so only the 4-boundary is usable
    assert c.lookup(p[:8])[0] == 4
    assert c.lookup(p[:4])[0] == 0   # no boundary strictly inside 4 tokens
    assert c.bind(4) is None and len(c) == 2
    with pytest.raises(ValueError):
        c.bind(8)  # engines sharing a cache must agree on boundaries


def test_poisoned_prefix_guard(monkeypatch):
    """A digest collision must NEVER splice foreign state: entries store
    the exact ids they hashed and a mismatch is rejected + counted."""
    c = PrefixCache(1 << 20)
    c.bind(4)
    monkeypatch.setattr(PrefixCache, "_key",
                        staticmethod(lambda tokens: "collide"))
    c.insert(np.arange(4, dtype=np.int32), _entry_state(8))
    c.insert(np.arange(4, dtype=np.int32) + 50, _entry_state(8))  # refresh-
    assert len(c) == 1                # by-key: everything hashes together
    p, e = c.lookup(np.array([9, 9, 9, 9, 1], np.int32))
    assert (p, e) == (0, None), "id mismatch at a matching digest hit!"
    assert c.stats()["collisions"] >= 1


# --- engine integration: hit == cold, bit-exactly ----------------------------


def _cached_engine(family, *, slots=2, chunk=4, budget=1 << 24, spec_k=0):
    rt, vocab, _ = _runtime(family)
    draft = speculative_draft(rt, mode="ternary") if spec_k else None
    return ServeEngine(rt, vocab, slots=slots, max_context=CTX,
                       prefill_chunk=chunk, prefix_cache=PrefixCache(budget),
                       draft=draft, spec_k=spec_k), vocab


@pytest.mark.parametrize("family", ["lstm-packed", "qwen3", "zamba2"])
def test_prefix_hit_resume_is_bit_exact(family):
    """Request 1 (cold) populates boundary snapshots; requests sharing its
    prefix splice instead of re-prefilling — and every stream matches the
    oracle bit for bit, hit or miss.  For the zamba2 hybrid the snapshot
    holds every mamba layer's h and conv tail beside the calls' narrowed
    K/V."""
    eng, vocab = _cached_engine(family)
    rng = np.random.default_rng(5)
    system = rng.integers(0, vocab, size=9).astype(np.int32)  # 2 boundaries
    mk = lambda tail, seed: Request(
        prompt=np.concatenate([system, tail]).astype(np.int32),
        max_tokens=6, temperature=0.8, top_k=5, seed=seed)
    cold = mk(rng.integers(0, vocab, size=3), 11)
    same = dataclasses.replace(cold)                      # identical prompt
    fork = mk(rng.integers(0, vocab, size=5), 13)         # shared system

    c1, _ = eng.run([dataclasses.replace(cold)], realtime=False)
    assert c1[0].cached_tokens == 0 and eng.prefix_cache.stats()["misses"] == 1
    ins = eng.prefix_cache.stats()["insertions"]
    assert ins >= 2  # the 4- and 8-boundaries of the 12-token prompt

    c2, m2 = eng.run([same], realtime=False)
    assert c2[0].cached_tokens == 8, "longest boundary prefix must splice"
    c3, _ = eng.run([fork], realtime=False)
    assert c3[0].cached_tokens == 8

    exp = _expected(family, cold)
    assert c1[0].tokens == exp, "cold stream diverged from oracle"
    assert c2[0].tokens == exp, "HIT stream != COLD stream"
    assert c3[0].tokens == _expected(family, fork)
    assert m2["splice_traces"] == 1 and eng.tick_traces == 1
    s = eng.prefix_cache.stats()
    assert s["hits"] == 2 and s["hit_tokens"] == 16 and s["collisions"] == 0


def test_prefix_hit_under_speculative_decoding():
    """Spec engines snapshot BOTH pools: a spliced prefix must leave the
    draft in lockstep, or acceptance (and at temp 0, correctness of the
    one-trace invariant checks) would silently degrade."""
    eng, vocab = _cached_engine("lstm-fp", spec_k=3)
    rng = np.random.default_rng(6)
    system = rng.integers(0, vocab, size=10).astype(np.int32)
    mk = lambda tail, seed: Request(
        prompt=np.concatenate([system, tail]).astype(np.int32),
        max_tokens=8, temperature=0.0, top_k=0, seed=seed)
    a = mk(rng.integers(0, vocab, size=2), 21)
    b = mk(rng.integers(0, vocab, size=4), 22)
    ca, _ = eng.run([a], realtime=False)
    cb, mb = eng.run([b], realtime=False)
    assert cb[0].cached_tokens == 8
    assert ca[0].tokens == _expected("lstm-fp", a)
    assert cb[0].tokens == _expected("lstm-fp", b)
    assert eng.spec_traces == 1 and mb["splice_traces"] == 1
    e = next(iter(eng.prefix_cache._entries.values()))
    assert e.draft_state is not None, "spec entries must carry the draft half"


def test_engine_eviction_keeps_streams_exact():
    """A budget that can only hold ~one boundary forces eviction churn mid-
    workload; evicted prefixes silently fall back to cold prefill and the
    bytes never change."""
    rt, vocab, _ = _runtime("lstm-packed")
    one = tree_bytes(rt.init_state(1, CTX, per_slot=True))
    eng, _ = _cached_engine("lstm-packed", budget=2 * one)
    rng = np.random.default_rng(7)
    reqs = [Request(prompt=rng.integers(0, vocab, size=9).astype(np.int32),
                    max_tokens=5, temperature=0.8, top_k=5, seed=30 + i)
            for i in range(4)]
    comps, m = eng.run([dataclasses.replace(r) for r in reqs],
                       realtime=False)
    s = eng.prefix_cache.stats()
    assert s["evictions"] >= 1 and s["bytes"] <= 2 * one
    for c, r in zip(sorted(comps, key=lambda c: c.rid), reqs):
        assert c.tokens == _expected("lstm-packed", r)
    assert eng.tick_traces == 1


def test_unsupported_runtime_is_refused():
    """'whole'-granularity runtimes have no exact chunk boundaries to key —
    the constructor must refuse rather than serve approximate state."""
    import types

    rt, vocab, _ = _runtime("lstm-packed")
    shim = types.SimpleNamespace(family=rt.family, extras=None,
                                 pad_buckets=False,
                                 chunk_granularity="whole")
    with pytest.raises(NotImplementedError):
        ServeEngine(shim, vocab, slots=1, max_context=CTX,
                    prefix_cache=PrefixCache(1 << 20))
