"""Continuous-batching serve engine (DESIGN.md §7): slot surgery must be
exact, the decode tick must compile once regardless of occupancy churn, and
a request's token stream through the engine must be BYTE-IDENTICAL to
running it alone through the sequential `drive_session` loop — continuous
batching changes the schedule, never the tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import bnlstm as BL
from repro.core.quantize import QuantSpec
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine, tree_write_slot
from repro.serve.kvcache import (cache_init, cache_positions, cache_reset_slots,
                                 cache_update, cache_write_slot)
from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                   drive_session, serving_runtime)
from repro.serve.sampler import sample, sample_slots


def _rnn_cfg(cell, mode="ternary"):
    return BL.RNNConfig(vocab=24, d_hidden=48, n_layers=2, cell=cell,
                        quant=QuantSpec(mode=mode, norm="batch"))


def _rnn_runtime(cell, packed=True, seed=0):
    cfg = _rnn_cfg(cell) if packed else dataclasses.replace(
        _rnn_cfg(cell), quant=QuantSpec(mode="none"))
    var = BL.rnn_lm_init(jax.random.PRNGKey(seed), cfg)
    params = var["params"]
    if packed:
        params = BL.export_packed_rnn(params, cfg)
    return cfg, RNNRuntime(cfg, {"params": params, "state": var["state"]})


def _requests(vocab, n, *, rng_seed=0, max_prompt=10, max_gen=8):
    rng = np.random.default_rng(rng_seed)
    return [Request(prompt=rng.integers(0, vocab,
                                        size=int(rng.integers(2, max_prompt))),
                    max_tokens=int(rng.integers(1, max_gen)),
                    temperature=0.8, top_k=5, seed=100 + i, rid=i)
            for i in range(n)]


# --- per-slot sampler: bit-parity with the scalar path -----------------------


def test_sample_slots_matches_scalar_sample_per_row():
    logits = jax.random.normal(jax.random.PRNGKey(3), (6, 33))
    temps = jnp.array([0.8, 0.0, 1.3, 0.5, 2.0, 0.8])
    topks = jnp.array([4, 0, 0, 7, 2, 33], jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(6)])
    vec = sample_slots(logits, keys, temperature=temps, top_k=topks, vocab=30)
    for i in range(6):
        ref = sample(logits[i:i + 1], keys[i], temperature=float(temps[i]),
                     top_k=int(topks[i]), vocab=30)[0]
        assert int(ref) == int(vec[i])


# --- slot surgery ------------------------------------------------------------


def test_rnn_write_and_reset_slots():
    from repro.serve.engine import tree_reset_slots
    cfg = _rnn_cfg("lstm")
    pool = BL.rnn_state_init(cfg, 4, per_slot=True)
    assert pool.pos.shape == (4,)
    sub = BL.RNNState(h=jnp.ones((cfg.n_layers, 1, cfg.d_hidden)),
                      c=2 * jnp.ones((cfg.n_layers, 1, cfg.d_hidden)),
                      pos=jnp.array([7], jnp.int32))
    pool = BL.rnn_write_slots(pool, sub, 2)
    assert float(pool.h[:, 2].min()) == 1.0 and float(pool.c[:, 2].max()) == 2.0
    assert pool.pos.tolist() == [0, 0, 7, 0]
    assert float(jnp.abs(pool.h[:, [0, 1, 3]]).max()) == 0.0  # others untouched
    # the engine's shape-aware scrub (the ONE retire path) zeroes h/c/pos
    ref = BL.rnn_state_init(cfg, 1, per_slot=True)
    pool = tree_reset_slots(pool, ref, jnp.array([False, False, True, False]))
    assert float(jnp.abs(pool.h).max()) == 0.0
    assert pool.pos.tolist() == [0, 0, 0, 0]


def test_cache_write_slot_and_reset():
    pool = cache_init(3, 8, 2, 4, jnp.float32, per_slot=True)
    sub = cache_init(1, 8, 2, 4, jnp.float32, per_slot=True)
    k = jnp.ones((1, 5, 2, 4))
    sub = cache_update(sub, k, 2 * k)  # write 5 tokens into the B=1 cache
    assert sub.pos.tolist() == [5]
    pool = cache_write_slot(pool, sub, 1)
    assert pool.pos.tolist() == [0, 5, 0]
    np.testing.assert_array_equal(np.asarray(pool.k[1]), np.asarray(sub.k[0]))
    kv = cache_positions(pool)  # (B, cap): only slot 1 has valid positions
    assert kv.shape == (3, 8)
    assert kv[1].tolist() == [0, 1, 2, 3, 4, -1, -1, -1]
    assert kv[0].tolist() == [-1] * 8
    pool = cache_reset_slots(pool, jnp.array([False, True, False]))
    assert pool.pos.tolist() == [0, 0, 0]
    assert cache_positions(pool)[1].tolist() == [-1] * 8  # masked, not resliced


def test_per_slot_cache_update_rows_are_independent():
    """Decode appends at each slot's OWN depth (the mixed-length invariant)."""
    pool = cache_init(3, 6, 1, 2, jnp.float32, per_slot=True)
    pool = pool._replace(pos=jnp.array([0, 2, 5], jnp.int32))
    k1 = jnp.arange(6, dtype=jnp.float32).reshape(3, 1, 1, 2) + 1
    pool = cache_update(pool, k1, k1)
    assert pool.pos.tolist() == [1, 3, 6]
    assert float(pool.k[0, 0, 0, 0]) == 1.0
    assert float(pool.k[1, 2, 0, 0]) == 3.0
    assert float(pool.k[2, 5, 0, 0]) == 5.0


def test_tree_write_slot_transformer_pool():
    """The generic writer finds the slot axis of every stacked cache leaf
    (axis 1 behind the layer stack, axis 0 for tail caches / pos)."""
    cfg = get_config("qwen3-0.6b").reduced()
    pool = T.init_caches(cfg, 3, 16, dtype=jnp.float32, per_slot=True)
    sub = T.init_caches(cfg, 1, 16, dtype=jnp.float32, per_slot=True)
    sub = jax.tree.map(lambda a: jnp.ones_like(a), sub)
    out = tree_write_slot(pool, sub, 1)
    for p, s in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(sub)):
        ax = next(i for i, (a, b) in enumerate(zip(p.shape, s.shape)) if a != b)
        row = jnp.take(p, 1, axis=ax).astype(jnp.float32)
        others = jnp.take(p, jnp.array([0, 2]), axis=ax).astype(jnp.float32)
        assert float(jnp.abs(row - 1.0).max()) == 0.0   # slot 1 took the sub
        assert float(jnp.abs(others).max()) == 0.0      # 0/2 untouched


# --- live-mask: dead slots are frozen bit-for-bit ----------------------------


@pytest.mark.parametrize("cell", ["lstm", "gru"])
@pytest.mark.parametrize("packed", [True, False], ids=["fused", "unfused"])
def test_decode_step_live_mask_freezes_dead_slots(cell, packed):
    cfg, rt = _rnn_runtime(cell, packed=packed)
    st = BL.rnn_state_init(cfg, 3, per_slot=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 3), 0, cfg.vocab)
    # walk all slots off zero first
    for i in range(2):
        _, st = rt.decode_fn(toks[i], st)
    live = jnp.array([True, False, True])
    lg, st2 = rt.decode_fn(toks[2], st, live)
    # dead slot 1: h/c/pos bit-identical
    np.testing.assert_array_equal(np.asarray(st2.h[:, 1]), np.asarray(st.h[:, 1]))
    np.testing.assert_array_equal(np.asarray(st2.c[:, 1]), np.asarray(st.c[:, 1]))
    assert st2.pos.tolist() == [3, 2, 3]
    # live slots: bit-identical to an unmasked step
    lg_all, st_all = rt.decode_fn(toks[2], st)
    np.testing.assert_array_equal(np.asarray(st2.h[:, 0]), np.asarray(st_all.h[:, 0]))
    np.testing.assert_array_equal(np.asarray(st2.h[:, 2]), np.asarray(st_all.h[:, 2]))
    np.testing.assert_array_equal(np.asarray(lg[0]), np.asarray(lg_all[0]))


# --- the acceptance bar: engine == sequential, token for token ---------------


@pytest.mark.parametrize("cell,packed", [("lstm", True), ("lstm", False),
                                         ("gru", True)],
                         ids=["lstm-packed", "lstm-fp", "gru-packed"])
def test_engine_matches_sequential_rnn(cell, packed):
    cfg, rt = _rnn_runtime(cell, packed=packed)
    reqs = _requests(cfg.vocab, 7, rng_seed=3)
    # prefill_chunk=4 < max prompt: the parity bar covers CHUNKED in-slot
    # prefill (multi-chunk prompts, bucket-padded tails), not just decode
    eng = ServeEngine(rt, cfg.vocab, slots=3, max_context=64,
                      prefill_chunk=4)
    comps, m = eng.run([dataclasses.replace(r) for r in reqs], realtime=False)
    assert m["requests"] == len(reqs)
    by_rid = {c.rid: c for c in comps}
    for r in reqs:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed)
        assert by_rid[r.rid].tokens == out[0].tolist()  # atol 0: identical


@pytest.mark.parametrize("packed", [False, True], ids=["fp", "packed"])
def test_engine_matches_sequential_transformer(packed):
    cfg = get_config("qwen3-0.6b").reduced()
    if packed:
        cfg = cfg.with_quant(QuantSpec(mode="ternary", norm="channel"))
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    if packed:
        from repro.core.qtensor import export_packed
        params = export_packed(params, cfg.quant)
    rt = TransformerRuntime(cfg, params)
    reqs = _requests(cfg.vocab, 4, rng_seed=5, max_prompt=8, max_gen=5)
    CTX = 48
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=CTX,
                      prefill_chunk=3)
    comps, _ = eng.run([dataclasses.replace(r) for r in reqs], realtime=False)
    by_rid = {c.rid: c for c in comps}
    for r in reqs:
        # same provisioned context so the sequential baseline attends over
        # an identically-sized (masked) cache
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed, context=CTX)
        assert by_rid[r.rid].tokens == out[0].tolist()


def test_engine_matches_sequential_ring_cache():
    """gemma3's local layers use ring (sliding-window) KV buffers: the
    per-slot scatter append + per-slot ring cache_positions must reproduce
    the scalar lockstep path token-for-token."""
    cfg = get_config("gemma3-27b").reduced()
    assert "local" in cfg.block_pattern  # the arch actually exercises rings
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    rt = TransformerRuntime(cfg, params)
    reqs = _requests(cfg.vocab, 3, rng_seed=7, max_prompt=7, max_gen=4)
    CTX = 24
    # ring caches chunk at exact lengths (no bucket padding: pad writes
    # would recycle in-window slots) — still multi-chunk at chunk 3
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=CTX,
                      prefill_chunk=3)
    comps, _ = eng.run([dataclasses.replace(r) for r in reqs], realtime=False)
    by_rid = {c.rid: c for c in comps}
    for r in reqs:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed, context=CTX)
        assert by_rid[r.rid].tokens == out[0].tolist()


def test_engine_staggered_arrivals_change_schedule_not_tokens():
    """Arrival order / slot assignment must not leak into any stream."""
    cfg, rt = _rnn_runtime("lstm")
    reqs = _requests(cfg.vocab, 6, rng_seed=11)
    for i, r in enumerate(reqs):
        r.arrival_s = 0.01 * (len(reqs) - i)  # reversed admission order
    a, _ = ServeEngine(rt, cfg.vocab, slots=2, max_context=64).run(
        [dataclasses.replace(r) for r in reqs], realtime=False)
    for r in reqs:
        r.arrival_s = 0.0
    b, _ = ServeEngine(rt, cfg.vocab, slots=3, max_context=64).run(
        [dataclasses.replace(r) for r in reqs], realtime=False)
    ta = {c.rid: c.tokens for c in a}
    tb = {c.rid: c.tokens for c in b}
    assert ta == tb


def test_engine_eos_retires_slot():
    cfg, rt = _rnn_runtime("lstm")
    probe, _ = drive_session(rt, jnp.zeros((1, 3), jnp.int32), cfg.vocab,
                             gen=6, temperature=0.8, top_k=0, seed=42)
    stream = probe[0].tolist()
    eos = stream[2]  # force an EOS hit mid-stream
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64, eos_id=eos)
    comps, _ = eng.run([Request(prompt=np.zeros(3, np.int64), max_tokens=6,
                                temperature=0.8, top_k=0, seed=42)],
                       realtime=False)
    c = comps[0]
    assert c.finished == "eos"
    assert c.tokens == stream[:c.tokens.index(eos) + 1]
    assert not eng._live_host.any()


def test_engine_rejects_invalid_requests_upfront():
    """A bad request must fail BEFORE anything is in flight (never mid-run),
    and the engine must never mutate the caller's Request objects."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=8)
    with pytest.raises(ValueError, match="max_tokens"):
        eng.run([Request(prompt=np.zeros(2, np.int32), max_tokens=0)])
    with pytest.raises(ValueError, match="max_context"):
        eng.run([Request(prompt=np.zeros(6, np.int32), max_tokens=8)])
    r = Request(prompt=np.zeros(2, np.int32), max_tokens=2)
    comps, _ = eng.run([r], realtime=False)
    assert r.rid is None and comps[0].rid == 0


# --- chunked in-slot prefill units -------------------------------------------


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_rnn_prefill_chunk_matches_prefill(cell):
    """A bucket-padded chunk sequence == one unpadded rnn_prefill, bit for
    bit: state after the real tokens, logits at the last real token."""
    cfg, rt = _rnn_runtime(cell)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 11), 0, cfg.vocab)
    st_ref = BL.rnn_state_init(cfg, 1, per_slot=True)
    _, st_ref = BL.rnn_prefill(rt.variables, toks, cfg, st_ref,
                               tables=rt.tables)
    lg_ref = BL.rnn_logits_last(rt.variables, st_ref, cfg)
    st = BL.rnn_state_init(cfg, 1, per_slot=True)
    for lo, hi, bucket in [(0, 4, 4), (4, 8, 4), (8, 11, 4)]:  # 3 real, pad 1
        pad = jnp.zeros((1, bucket), toks.dtype)
        chunk = jax.lax.dynamic_update_slice(pad, toks[:, lo:hi], (0, 0))
        lg, st = BL.rnn_prefill_chunk(rt.variables, chunk, cfg, st,
                                      n=hi - lo, tables=rt.tables)
    np.testing.assert_array_equal(np.asarray(st.h), np.asarray(st_ref.h))
    np.testing.assert_array_equal(np.asarray(st.c), np.asarray(st_ref.c))
    assert st.pos.tolist() == [11]
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))


def test_tree_gather_slot_inverts_tree_write_slot():
    """read half of the in-slot surgery: gather(write(pool, sub, s), s) == sub
    for every leaf of a transformer cache pool (stacked + tail axes)."""
    from repro.serve.engine import tree_gather_slot
    cfg = get_config("qwen3-0.6b").reduced()
    pool = T.init_caches(cfg, 3, 16, dtype=jnp.float32, per_slot=True)
    ref = jax.eval_shape(
        lambda: T.init_caches(cfg, 1, 16, dtype=jnp.float32, per_slot=True))
    sub = T.init_caches(cfg, 1, 16, dtype=jnp.float32, per_slot=True)
    sub = jax.tree.map(lambda a: jnp.ones_like(a), sub)
    pool = tree_write_slot(pool, sub, 2)
    back = tree_gather_slot(pool, ref, 2)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(sub)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transformer_decode_live_mask_freezes_dead_rows():
    """The decode tick must not touch a dead row's cache: with in-slot
    prefill a dead row can be MID-PREFILL, so zombie appends (bytes OR pos)
    would corrupt the prompt it is accumulating."""
    cfg = get_config("qwen3-0.6b").reduced()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    rt = TransformerRuntime(cfg, params)
    st = rt.init_state(3, 16, per_slot=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 3), 0, cfg.vocab)
    _, st = rt.prefill(toks, st)
    live = jnp.array([True, False, True])
    _, st2 = jax.jit(rt.decode_fn)(jnp.array([1, 2, 3]), st, live)
    # dead row 1: every cache leaf bit-identical; live rows advanced pos
    ref = jax.eval_shape(lambda: rt.init_state(1, 16, per_slot=True))
    from repro.serve.engine import tree_gather_slot
    row_before = tree_gather_slot(st, ref, 1)
    row_after = tree_gather_slot(st2, ref, 1)
    for a, b in zip(jax.tree_util.tree_leaves(row_before),
                    jax.tree_util.tree_leaves(row_after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    live_rows = tree_gather_slot(st2, ref, 0)
    prev_rows = tree_gather_slot(st, ref, 0)
    changed = any(not np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree_util.tree_leaves(live_rows),
                                  jax.tree_util.tree_leaves(prev_rows)))
    assert changed  # live rows really stepped


def test_engine_matches_sequential_hybrid_ssm():
    """zamba2 (mamba + shared attention): 'token' chunk granularity with
    exact bucket padding, and the decode tick's recurrent-state freeze
    (_freeze_dead) must keep a dead slot's S-matrices / conv tails / K/V
    bit-frozen — with in-slot prefill a dead row can be mid-prefill, so
    this is load-bearing, not cosmetic."""
    from repro.serve.engine import tree_gather_slot
    cfg = get_config("zamba2-1.2b").reduced()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    rt = TransformerRuntime(cfg, params)
    assert rt.chunk_granularity == "token" and rt.pad_buckets
    assert not rt.spec_capable

    # dead-row freeze across EVERY pool leaf (ssm h/conv/pos included)
    st = rt.init_state(2, 16, per_slot=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, cfg.vocab)
    _, st = rt.prefill(toks, st)
    _, st2 = jax.jit(rt.decode_fn)(jnp.array([1, 2]), st,
                                   jnp.array([False, True]))
    ref = jax.eval_shape(lambda: rt.init_state(1, 16, per_slot=True))
    for a, b in zip(jax.tree_util.tree_leaves(tree_gather_slot(st, ref, 0)),
                    jax.tree_util.tree_leaves(tree_gather_slot(st2, ref, 0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and the engine over the hybrid still streams byte-identically
    reqs = _requests(cfg.vocab, 2, rng_seed=37, max_prompt=6, max_gen=4)
    CTX = 20
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=CTX,
                      prefill_chunk=4)
    comps, m = eng.run([dataclasses.replace(r) for r in reqs],
                       realtime=False)
    assert m["tick_traces"] == 1
    by_rid = {c.rid: c for c in comps}
    for r in reqs:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed, context=CTX)
        assert by_rid[r.rid].tokens == out[0].tolist()


# --- TTFT semantics + scheduling guarantees ----------------------------------


def test_completion_timestamps_are_ordered():
    """t_submit <= t_admit <= t_first <= t_done for every completion of a
    mixed realtime workload — t_first is stamped when the first token is
    actually sampled (after the last prompt chunk), not at admission."""
    cfg, rt = _rnn_runtime("lstm")
    reqs = _requests(cfg.vocab, 6, rng_seed=13, max_prompt=12)
    for i, r in enumerate(reqs):
        r.arrival_s = 0.002 * i
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4)
    comps, _ = eng.run(reqs, realtime=True)
    assert len(comps) == len(reqs)
    for c in comps:
        assert c.t_submit <= c.t_admit <= c.t_first <= c.t_done
        assert c.ttft_s >= 0 and c.queue_s >= 0
    # multi-chunk prompts really did sample their first token after admit
    long = [c for c in comps if c.prompt_len > 4]
    assert long and all(c.t_first > c.t_admit for c in long)


def test_long_prompt_does_not_stall_decodes():
    """Head-of-line blocking is gone: while a 40-token prompt prefills in
    2-token chunks, a live short request keeps decoding every tick and
    finishes BEFORE the long prompt's first token; no admission ever runs
    more than one chunk between decode ticks."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=2)
    rng = np.random.default_rng(0)
    short = Request(prompt=rng.integers(0, cfg.vocab, size=2), max_tokens=6,
                    temperature=0.8, top_k=5, seed=7, rid=0, arrival_s=0.0)
    long = Request(prompt=rng.integers(0, cfg.vocab, size=40), max_tokens=2,
                   temperature=0.8, top_k=5, seed=8, rid=1, arrival_s=0.0)
    comps, m = eng.run([short, long], realtime=False)
    assert m["max_decode_stall_ticks"] <= 1
    by = {c.rid: c for c in comps}
    assert by[0].t_done < by[1].t_first  # short finished mid-long-prefill
    for r in (short, long):  # and the interleaving changed no bytes
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed)
        assert by[r.rid].tokens == out[0].tolist()


# --- engine edge cases -------------------------------------------------------


def test_prompt_exactly_fills_context():
    """prompt == max_context - 1 with max_tokens == 1 is the largest legal
    request; it must admit, chunk, sample and retire cleanly."""
    cfg, rt = _rnn_runtime("lstm")
    CTX = 16
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=CTX,
                      prefill_chunk=4)
    prompt = np.arange(CTX - 1, dtype=np.int32) % cfg.vocab
    comps, _ = eng.run([Request(prompt=prompt, max_tokens=1, seed=3, rid=0)],
                       realtime=False)
    out, _ = drive_session(rt, jnp.asarray(prompt)[None], cfg.vocab, gen=1,
                           temperature=0.8, top_k=0, seed=3)
    assert comps[0].tokens == out[0].tolist()
    assert comps[0].finished == "length"
    assert not eng._live_host.any() and eng._free_slot() == 0


def test_eos_on_admission_token():
    """EOS hit by the very first sampled token: the request completes when
    that token is read, one iteration after its last chunk.  The slot went
    live with the commit, so the two ticks dispatched before the read were
    fed it; both of their tokens are dropped, and the slot is free."""
    cfg, rt = _rnn_runtime("lstm")
    probe, _ = drive_session(rt, jnp.zeros((1, 5), jnp.int32), cfg.vocab,
                             gen=1, temperature=0.8, top_k=0, seed=11)
    eos = probe[0].tolist()[0]
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64, eos_id=eos,
                      prefill_chunk=2)
    ticks0 = eng.ticks
    comps, _ = eng.run([Request(prompt=np.zeros(5, np.int32), max_tokens=8,
                                temperature=0.8, top_k=0, seed=11)],
                       realtime=False)
    assert comps[0].finished == "eos" and comps[0].tokens == [eos]
    assert eng.ticks == ticks0 + 2
    assert eng.stats()["discarded_tokens"] == 2
    assert comps[0].t_first == comps[0].t_done
    assert eng._free_slot() == 0


def test_rejected_request_does_not_poison_inflight_workload():
    """Validation fails BEFORE anything enters a slot, and a rejected
    run() leaves the engine fully serviceable: the next workload still
    matches the sequential oracle with the tick never retracing."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=16,
                      prefill_chunk=4)
    good = _requests(cfg.vocab, 3, rng_seed=17, max_prompt=8, max_gen=5)
    bad = Request(prompt=np.zeros(14, np.int32), max_tokens=8)  # 14+8 > 16
    with pytest.raises(ValueError, match="max_context"):
        eng.run([dataclasses.replace(good[0]), bad], realtime=False)
    assert not eng._live_host.any() and not eng._prefill_q
    comps, m = eng.run([dataclasses.replace(r) for r in good],
                       realtime=False)
    assert m["tick_traces"] == 1
    by_rid = {c.rid: c for c in comps}
    for r in good:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed)
        assert by_rid[r.rid].tokens == out[0].tolist()


def test_run_twice_reuses_slots_cleanly():
    """Back-to-back workloads on ONE engine: freed slots are scrubbed and
    reused, and the second wave's streams still match the oracle exactly
    (nothing from wave 1 leaks through a reused slot row)."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4)
    eng.run(_requests(cfg.vocab, 4, rng_seed=19), realtime=False)
    wave2 = _requests(cfg.vocab, 4, rng_seed=23)
    comps, m = eng.run([dataclasses.replace(r) for r in wave2],
                       realtime=False)
    assert m["tick_traces"] == 1
    by_rid = {c.rid: c for c in comps}
    for r in wave2:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed)
        assert by_rid[r.rid].tokens == out[0].tolist()
    assert {c.slot for c in comps} <= {0, 1}  # same two slots, recycled


# --- the compile-once invariant ----------------------------------------------


@pytest.mark.parametrize("family", ["rnn", "qwen3"])
def test_warm_buckets_then_run_traces_nothing(family):
    """After warm() compiles the declared chunk buckets, a measured run()
    performs ZERO new traces — prefill included, not just the decode tick.
    Bucket padding is what makes the declared set traffic-independent."""
    if family == "rnn":
        cfg, rt = _rnn_runtime("lstm")
        vocab, ctx = cfg.vocab, 64
        reqs = _requests(vocab, 6, rng_seed=29, max_prompt=13)
    else:
        cfg = get_config("qwen3-0.6b").reduced()
        params = T.model_init(jax.random.PRNGKey(0), cfg)
        rt = TransformerRuntime(cfg, params)
        vocab, ctx = cfg.vocab, 32
        reqs = _requests(vocab, 3, rng_seed=29, max_prompt=9, max_gen=4)
    eng = ServeEngine(rt, vocab, slots=2, max_context=ctx, prefill_chunk=4)
    eng.warm()  # NO prompt lengths: the declared buckets must suffice
    pt, tt = eng.prefill_traces, eng.tick_traces
    assert tt == 1 and pt == len(eng.declared_buckets())
    comps, m = eng.run(reqs, realtime=False)
    assert len(comps) == len(reqs)
    assert eng.prefill_traces == pt, "a prompt length traced a new prefill"
    assert eng.tick_traces == 1, "occupancy churn retraced the tick"


def test_tick_compiles_once_across_occupancy_churn():
    """Admits and retires between ticks must NOT retrace the decode tick —
    occupancy is an array value, not a shape."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=3, max_context=64)
    # wave 1: overfull queue -> admission churn as slots free up
    eng.run(_requests(cfg.vocab, 5, rng_seed=21), realtime=False)
    assert eng.tick_traces == 1
    # wave 2: different occupancy pattern on the SAME engine
    eng.run(_requests(cfg.vocab, 2, rng_seed=22, max_gen=4), realtime=False)
    assert eng.tick_traces == 1
    assert eng.ticks > 2


def test_pool_state_is_constant_shape():
    """mask-don't-reshape: the pool pytree never changes shape over a run."""
    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64)
    shapes0 = [l.shape for l in jax.tree_util.tree_leaves(eng.pool)]
    eng.run(_requests(cfg.vocab, 4, rng_seed=31), realtime=False)
    assert [l.shape for l in jax.tree_util.tree_leaves(eng.pool)] == shapes0


def test_params_are_jit_arguments_not_baked_constants():
    """The engine's tick/prefill jits take the weight tree as an ARGUMENT
    (`rt.jit_prm`), never a closure capture: closed-over weights get
    constant-folded by XLA, which shifts logits ~1ulp against the
    arg-passed `drive_session` jits and makes logits-level comparisons
    unsound.  The observable property: swapping in a differently-
    initialised tree of the same shape changes the streams WITHOUT a
    single new trace — impossible if the weights were baked in."""
    cell = "lstm"
    cfg = dataclasses.replace(_rnn_cfg(cell), quant=QuantSpec(mode="none"))
    var1 = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
    var2 = BL.rnn_lm_init(jax.random.PRNGKey(9), cfg)
    rt1 = RNNRuntime(cfg, {"params": var1["params"], "state": var1["state"]})
    rt2 = RNNRuntime(cfg, {"params": var2["params"], "state": var2["state"]})
    eng = ServeEngine(rt1, cfg.vocab, slots=1, max_context=64,
                      prefill_chunk=4)
    req = Request(prompt=np.arange(8, dtype=np.int32) % cfg.vocab,
                  max_tokens=10, temperature=0.0, top_k=0, seed=5)
    c1, _ = eng.run([dataclasses.replace(req)], realtime=False)
    traces = (eng.tick_traces, eng.prefill_traces)
    assert traces[0] == 1
    eng._prms = (rt2.jit_prm,)  # same treedef/avals, different weights
    c2, _ = eng.run([dataclasses.replace(req)], realtime=False)
    assert (eng.tick_traces, eng.prefill_traces) == traces, \
        "swapping the param ARGUMENT must not retrace anything"
    assert c1[0].tokens != c2[0].tokens, \
        "greedy streams ignored the swapped weights — params are baked in"
    # and the swapped-in tree drives the engine to rt2's own oracle stream
    out2, _ = drive_session(rt2, jnp.asarray(req.prompt)[None], cfg.vocab,
                            gen=req.max_tokens, temperature=0.0, top_k=0,
                            seed=req.seed)
    assert c2[0].tokens == out2[0].tolist()


# --- deferred reads: the settled view, the discard rule, the counters --------


def test_settled_pool_matches_recorded_tokens_after_every_step():
    """Reading `pool` settles the outstanding read: after every step() of a
    mixed-length run, each decoding slot's row has consumed its prompt and
    every recorded token but the last, and each prefilling slot exactly the
    prompt tokens chunked so far.  Settling changes no stream."""
    cfg, rt = _rnn_runtime("lstm")
    reqs = _requests(cfg.vocab, 6, rng_seed=41, max_prompt=12, max_gen=9)
    eng = ServeEngine(rt, cfg.vocab, slots=3, max_context=64,
                      prefill_chunk=4)
    rids = [eng.submit(dataclasses.replace(r)) for r in reqs]
    got = {rid: [] for rid in rids}
    checked = 0
    while eng.has_work():
        for rid, toks in eng.step()[0]:
            got[rid].extend(toks)
        pos = np.asarray(eng.pool.pos)
        for slot, act in enumerate(eng._active):
            if act is None:
                continue
            want = (act.prompt.size + len(act.tokens) - 1 if act.tokens
                    else act.off)
            assert pos[slot] == want, (slot, act.rid)
            checked += 1
    assert checked > 0 and eng.stats()["settles"] > 0
    for r in reqs:
        out, _ = drive_session(
            rt, jnp.asarray(np.asarray(r.prompt, np.int32))[None], cfg.vocab,
            gen=r.max_tokens, temperature=r.temperature, top_k=r.top_k,
            seed=r.seed)
        assert got[r.rid] == out[0].tolist()


def _eos_mid_stream(rt, vocab):
    """(prompt, seed, eos): a stream whose third token is its first EOS."""
    for seed in range(40, 80):
        probe, _ = drive_session(rt, jnp.zeros((1, 3), jnp.int32), vocab,
                                 gen=6, temperature=0.8, top_k=0, seed=seed)
        s = probe[0].tolist()
        if s[2] not in s[:2]:
            return np.zeros(3, np.int32), seed, s[2]
    raise AssertionError("no seed gives a fresh third token")


@pytest.mark.parametrize("stop", ["eos", "max_tokens_1"])
def test_a_stopped_request_drops_one_token(stop):
    """A request stopped by EOS, or by a quota of one, with a tick in
    flight: exactly one token (the in-flight tick's) is dropped, and the
    next occupant of the slot streams exactly what a fresh engine gives."""
    cfg, rt = _rnn_runtime("lstm")
    prompt, seed, eos = _eos_mid_stream(rt, cfg.vocab)
    stopped = Request(prompt=prompt, max_tokens=6, temperature=0.8,
                      top_k=0, seed=seed, rid=0)
    if stop == "max_tokens_1":
        stopped = dataclasses.replace(stopped, max_tokens=1)
        eos = None
    nxt = _requests(cfg.vocab, 1, rng_seed=43)[0]
    nxt = dataclasses.replace(nxt, max_tokens=7, rid=1)

    def engine():
        return ServeEngine(rt, cfg.vocab, slots=1, max_context=64,
                           prefill_chunk=4, eos_id=eos)

    eng = engine()
    comps, _ = eng.run([stopped, dataclasses.replace(nxt)], realtime=False)
    by = {c.rid: c for c in comps}
    assert eng.stats()["discarded_tokens"] == 1
    if stop == "eos":
        assert by[0].finished == "eos" and len(by[0].tokens) == 3
    else:
        assert by[0].finished == "length" and len(by[0].tokens) == 1
    assert by[1].slot == by[0].slot == 0
    fresh, _ = engine().run([dataclasses.replace(nxt)], realtime=False)
    assert by[1].tokens == fresh[0].tokens


def test_cancel_with_a_tick_in_flight_returns_settled_tokens():
    """cancel() settles the tick in flight first: the completion holds its
    token too, that token is oracle-exact, and the next step() returns it
    as the request's last event."""
    cfg, rt = _rnn_runtime("lstm")
    req = Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab,
                  max_tokens=20, temperature=0.8, top_k=5, seed=9, rid=0)
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4)
    eng.submit(dataclasses.replace(req))
    got = []
    while len(got) < 3:
        for _, toks in eng.step()[0]:
            got.extend(toks)
    assert any(u.kind == "tick" for u in eng._unread)  # a tick in flight
    comp = eng.cancel(0)
    assert comp.finished == "cancelled" and eng.stats()["settles"] == 1
    assert comp.tokens == got + [comp.tokens[-1]]
    out, _ = drive_session(rt, jnp.asarray(req.prompt)[None], cfg.vocab,
                           gen=req.max_tokens, temperature=req.temperature,
                           top_k=req.top_k, seed=req.seed)
    assert comp.tokens == out[0].tolist()[:len(comp.tokens)]
    events, comps = eng.step()
    assert events == [(0, [comp.tokens[-1]])] and comps == []
    assert not eng.has_work() and eng._free_slot() == 0


def test_scripted_run_counts_discards_settles_and_ticks_ahead(tmp_path):
    """A run whose every read is known in advance.  Two slots; A (quota 4)
    and B (quota 1) each commit their one-chunk prompt:
      step 1  A commits; tick 1 feeds A
      step 2  B commits; tick 2 feeds A and B, the first tick dispatched
              with an earlier tick unread (ahead 1); B's quota is full, so
              B leaves; A's first token and tick 1 are read
      pool    settle 1: B's first token, tick 2 (B's token dropped: 1)
      step 3  tick 3 feeds A (nothing unread: not ahead); A's quota fills
      cancel  settle 2: tick 3, A's last token; A is done, so None
      step 4  returns what settle 2 read.
    The contexts the ticks attend over (prompt + tokens sampled so far)
    are 4, then 5 + 3, then 6; A's 3-token prompt is padded to a bucket
    of 4, B's 2 tokens fill theirs."""
    from repro.serve import telemetry

    cfg, rt = _rnn_runtime("lstm")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4)
    eng.warm()
    d0, s0 = eng.stats()["discarded_tokens"], eng.stats()["settles"]
    ticks0 = eng.ticks
    a = Request(prompt=np.array([1, 2, 3], np.int32), max_tokens=4,
                temperature=0.0, seed=1, rid=10)
    b = Request(prompt=np.array([4, 5], np.int32), max_tokens=1,
                temperature=0.0, seed=2, rid=11)
    got, comps = {10: [], 11: []}, []

    def step():
        events, cs = eng.step()
        for rid, toks in events:
            got[rid].extend(toks)
        comps.extend(cs)

    with jax.profiler.trace(str(tmp_path)):
        eng.submit(a)
        eng.submit(b)
        step()
        step()
        assert got == {10: [got[10][0], got[10][1]], 11: []}
        eng.pool
        step()
        assert eng.cancel(10) is None
        step()
        counts = telemetry.snapshot()["counts"]
    assert not eng.has_work()
    assert eng.ticks - ticks0 == 3
    assert eng.stats()["discarded_tokens"] - d0 == 1
    assert eng.stats()["settles"] - s0 == 2
    assert counts == {"serve.ticks_ahead": 1,
                      "serve.live_context": 4 + (5 + 3) + 6,
                      "serve.prefill_pad": 1 + 0}
    assert [c.rid for c in comps] == [11, 10]
    assert len(got[10]) == 4 and len(got[11]) == 1
    assert {c.rid: c.tokens for c in comps} == got
