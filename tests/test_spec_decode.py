"""Speculative decoding (DESIGN.md §9): draft-verify-accept must change the
schedule, never the distribution — and at temperature 0, never a byte.

Four layers of proof:
  * sampler properties — the Leviathan identity q(v)·min(1, p(v)/q(v)) +
    P(reject)·residual(v) == p(v) holds for random (p, q) pairs.  Run as a
    seeded `random.Random` property loop (hypothesis is not installable in
    this environment, so a @given here would silently skip — the loop keeps
    the property coverage in tier-1);
  * statistical acceptance — frequency-testing `spec_accept` on a tiny
    vocab shows the emitted-token marginal matches the target distribution,
    and forcing p_draft == p_target accepts every draft;
  * rollback bit-exactness — committing 0 tokens of a verify restores the
    full state tree (RNN h/c/pos, transformer KV BYTES + pos) bit-for-bit,
    and committing j tokens equals j plain decode steps bit-for-bit;
  * engine invariants — a spec engine whose draft IS its target accepts
    everything; unsupported runtimes (ring caches, hybrids) are refused.
"""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import bnlstm as BL
from repro.core.quantize import QuantSpec
from repro.models import transformer as T
from repro.serve.engine import Request, ServeEngine
from repro.serve.kvcache import cache_spec_commit, cache_spec_snapshot
from repro.serve.prefixcache import PrefixCache
from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                   speculative_draft)
from repro.serve.sampler import (filtered_probs, residual_probs, sample_slots,
                                 spec_accept)


def _rnn_runtime(packed=False, seed=0):
    spec = (QuantSpec(mode="ternary", norm="batch") if packed
            else QuantSpec(mode="none"))
    cfg = BL.RNNConfig(vocab=24, d_hidden=48, n_layers=2, cell="lstm",
                       quant=spec)
    var = BL.rnn_lm_init(jax.random.PRNGKey(seed), cfg)
    params = var["params"]
    if packed:
        params = BL.export_packed_rnn(params, cfg)
    return cfg, RNNRuntime(cfg, {"params": params, "state": var["state"]})


# --- sampler properties: seeded random.Random loop (no hypothesis) -----------


def test_residual_identity_property_loop():
    """The rejection-sampling identity, the reason speculative output IS the
    target distribution: for every token v,
        q(v) * min(1, p(v)/q(v)) + (1 - sum_u q(u) min(1, p(u)/q(u))) * r(v)
    equals p(v), where r = residual_probs(p, q).  40 seeded random (p, q)
    pairs, including near-equal and disjoint-support shapes."""
    rng = random.Random(1234)
    for case in range(40):
        V = rng.randint(2, 12)
        logp = np.array([rng.gauss(0, 2) for _ in range(V)])
        if case % 4 == 0:      # near-identical distributions
            logq = logp + np.array([rng.gauss(0, 1e-3) for _ in range(V)])
        elif case % 4 == 1:    # near-disjoint support
            logq = np.roll(logp, 1) + np.array(
                [rng.gauss(0, 3) for _ in range(V)])
        else:
            logq = np.array([rng.gauss(0, 2) for _ in range(V)])
        p = np.exp(logp) / np.exp(logp).sum()
        q = np.exp(logq) / np.exp(logq).sum()
        r = np.asarray(residual_probs(jnp.asarray(p)[None],
                                      jnp.asarray(q)[None]))[0]
        acc = q * np.minimum(1.0, p / q)
        out = acc + (1.0 - acc.sum()) * r
        np.testing.assert_allclose(out, p, atol=1e-6,
                                   err_msg=f"identity failed (case {case})")
        assert r.min() >= 0 and abs(r.sum() - 1.0) < 1e-6


def test_residual_zero_mass_falls_back_to_target():
    p = jnp.array([[0.25, 0.75]])
    r = residual_probs(p, p)  # residual mass is exactly zero
    np.testing.assert_allclose(np.asarray(r), np.asarray(p))


def test_filtered_probs_matches_sample_slots_semantics():
    """filtered_probs is the distribution sample_slots draws from: one-hot
    at the greedy argmax for temperature <= 0, softmax of the SAME
    filtered/scaled logits otherwise (top-k zeroes everything below the
    k-th largest)."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 9))
    temps = jnp.array([0.0, 1.0, 0.7, 2.0])
    topks = jnp.array([0, 0, 3, 9], jnp.int32)
    P = filtered_probs(logits, temps, topks, vocab=7)
    P = np.asarray(P)
    # row 0: greedy one-hot at the vocab-masked argmax
    g = int(jnp.argmax(jnp.where(jnp.arange(9) < 7, logits[0], -jnp.inf)))
    assert P[0, g] == 1.0 and P[0].sum() == 1.0
    # vocab mask: padded ids carry zero mass in every row
    assert float(P[:, 7:].max()) == 0.0
    # row 2: top-3 keeps exactly 3 tokens with mass
    assert int((P[2] > 0).sum()) == 3
    np.testing.assert_allclose(P.sum(-1), 1.0, atol=1e-6)
    # stochastic rows: empirical sample_slots frequencies match
    N = 4000
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))
    row = jnp.broadcast_to(logits[2], (N, 9))
    draws = np.asarray(sample_slots(
        row, keys, temperature=jnp.full((N,), 0.7),
        top_k=jnp.full((N,), 3, jnp.int32), vocab=7))
    freq = np.bincount(draws, minlength=9) / N
    np.testing.assert_allclose(freq, P[2], atol=0.04)


# --- statistical acceptance ---------------------------------------------------


def _accept_batch(n, seed=0, *, equal=False, K=2, V=5):
    """spec_accept over n identical (p, q) slots with distinct keys: the
    per-slot vectorization doubles as a Monte Carlo harness."""
    kp, kq, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    p_logits = jnp.broadcast_to(jax.random.normal(kp, (K + 1, V)), (n, K + 1, V))
    q_row = p_logits[0, :K] if equal else jax.random.normal(kq, (K, V))
    q_logits = jnp.broadcast_to(q_row, (n, K, V))
    temp = jnp.ones((n,))
    topk = jnp.zeros((n,), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n))
    # drafts sampled from q per position, per slot — the spec tick's draft
    # loop with the state dependency cut (q is fixed per position here)
    dkeys = jax.vmap(lambda k: jax.random.split(k, K))(
        jax.vmap(jax.random.fold_in, (0, None))(keys, 7))
    drafts = jnp.stack(
        [sample_slots(q_logits[:, i], dkeys[:, i], temperature=temp,
                      top_k=topk, vocab=V) for i in range(K)], axis=1)
    n_acc, out = jax.jit(lambda *a: spec_accept(
        a[0], a[1], a[2], a[3], temperature=temp, top_k=topk, vocab=V))(
        p_logits, q_logits, drafts, keys)
    return np.asarray(p_logits[0]), np.asarray(n_acc), np.asarray(out)


def test_spec_accept_matches_target_distribution():
    """The first emitted token of every slot (draft-if-accepted else
    residual resample) must be distributed as the TARGET's position-0
    distribution — the output distribution is exactly p, never q."""
    p_logits, n_acc, out = _accept_batch(4000, seed=3)
    target = np.asarray(jax.nn.softmax(jnp.asarray(p_logits[0])))
    freq = np.bincount(out[:, 0], minlength=5) / len(out)
    np.testing.assert_allclose(freq, target, atol=0.04)
    assert n_acc.min() >= 1 and n_acc.max() <= 3


def test_spec_accept_equal_distributions_accept_everything():
    """p_draft == p_target: the ratio is 1 everywhere, every draft is
    accepted, and every slot emits the full K+1 (drafts + bonus)."""
    _, n_acc, _ = _accept_batch(500, seed=5, equal=True)
    assert (n_acc == 3).all()


def test_spec_accept_greedy_is_target_argmax():
    """temperature 0: whatever the drafts, the emitted prefix is exactly
    the target's greedy chain prefix."""
    V, K = 6, 3
    p_logits = jax.random.normal(jax.random.PRNGKey(2), (1, K + 1, V))
    greedy = np.asarray(jnp.argmax(p_logits[0], -1))
    for draft_case in range(5):
        drafts = jax.random.randint(jax.random.PRNGKey(draft_case),
                                    (1, K), 0, V)
        q_logits = jax.random.normal(jax.random.PRNGKey(draft_case + 10),
                                     (1, K, V))
        n_acc, out = spec_accept(
            p_logits, q_logits, drafts, jnp.asarray([[0, 1]], jnp.uint32),
            temperature=jnp.zeros((1,)), top_k=jnp.zeros((1,), jnp.int32),
            vocab=V)
        n = int(n_acc[0])
        assert np.asarray(out)[0, :n].tolist() == greedy[:n].tolist()


# --- verify: bit-parity with sequential decode --------------------------------


def test_rnn_verify_matches_sequential_decode_steps():
    cfg, rt = _rnn_runtime()
    B, K = 3, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, K), 0, cfg.vocab)
    st0 = BL.rnn_state_init(cfg, B, per_slot=True)
    _, st0 = rt.prefill(jax.random.randint(jax.random.PRNGKey(2), (B, 3),
                                           0, cfg.vocab), st0)
    # variables/tables as jit ARGS, matching rt.decode_step's compilation
    # (a closed-over tree constant-folds to ulp-different logits; the
    # engine closes over constants on BOTH sides of its parity bar, which
    # the fuzz harness proves at stream level)
    lgs, end, emits = jax.jit(
        lambda v, tb, tk, s: BL.rnn_verify(v, tk, cfg, s, tables=tb))(
        rt.variables, rt.tables, toks, st0)
    st = st0
    for i in range(K):
        lg, st = rt.decode_step(toks[:, i], st)
        np.testing.assert_array_equal(np.asarray(lgs[:, i]), np.asarray(lg))
    np.testing.assert_array_equal(np.asarray(end.h), np.asarray(st.h))
    np.testing.assert_array_equal(np.asarray(end.c), np.asarray(st.c))


def test_transformer_verify_matches_sequential_decode_steps():
    cfg = get_config("qwen3-0.6b").reduced()
    rt = TransformerRuntime(cfg, T.model_init(jax.random.PRNGKey(0), cfg))
    B, K = 2, 3
    st0 = rt.init_state(B, 24, per_slot=True)
    _, st0 = rt.prefill(jax.random.randint(jax.random.PRNGKey(2), (B, 4),
                                           0, cfg.vocab), st0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, K), 0, cfg.vocab)
    lgs, end, _ = jax.jit(rt.verify)(toks, st0)
    st = st0
    for i in range(K):
        lg, st = rt.decode_step(toks[:, i], st)
        np.testing.assert_array_equal(np.asarray(lgs[:, i]), np.asarray(lg))
    for a, b in zip(jax.tree_util.tree_leaves(end),
                    jax.tree_util.tree_leaves(st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- rollback bit-exactness ---------------------------------------------------


def test_rnn_rollback_restores_snapshot_bit_exact():
    """Reject-everything (n = 0): the committed tree is the pre-verify
    snapshot, bit for bit — h, c AND pos."""
    cfg, rt = _rnn_runtime()
    B = 2
    st0 = BL.rnn_state_init(cfg, B, per_slot=True)
    _, st0 = rt.prefill(jax.random.randint(jax.random.PRNGKey(3), (B, 5),
                                           0, cfg.vocab), st0)
    snap = jax.tree.map(lambda a: np.asarray(a).copy(), st0)
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 3), 0, cfg.vocab)
    _, end, emits = rt.verify(toks, st0)
    committed = rt.spec_commit(st0, end, (), emits, jnp.zeros((B,), jnp.int32))
    for a, b in zip(jax.tree_util.tree_leaves(committed),
                    jax.tree_util.tree_leaves(snap)):
        np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("n_commit", [0, 2])
def test_transformer_rollback_restores_kv_bytes_bit_exact(n_commit):
    """The KV rollback is byte surgery, not just pos masking: committing n
    of a verified span leaves the cache tree — bytes INCLUDED — bit-
    identical to a cache that plain-decoded exactly n of those tokens.
    n = 0 is the reject-at-position-0 case: the restored tree equals the
    pre-verify snapshot."""
    cfg = get_config("qwen3-0.6b").reduced()
    rt = TransformerRuntime(cfg, T.model_init(jax.random.PRNGKey(0), cfg))
    B, K = 2, 3
    st0 = rt.init_state(B, 24, per_slot=True)
    _, st0 = rt.prefill(jax.random.randint(jax.random.PRNGKey(2), (B, 4),
                                           0, cfg.vocab), st0)
    snap_tree = jax.tree.map(
        lambda a: np.asarray(a).copy(), st0,
        is_leaf=lambda x: hasattr(x, "dtype"))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, K), 0, cfg.vocab)

    snap = rt.spec_snapshot(st0, K)
    _, after, _ = rt.verify(toks, st0)
    n = jnp.full((B,), n_commit, jnp.int32)
    committed = rt.spec_commit(st0, after, snap, (), n)

    if n_commit == 0:
        ref = st0  # the pre-verify tree, bytes and all
    else:
        ref = st0
        for i in range(n_commit):
            _, ref = rt.decode_step(toks[:, i], ref)
    for a, b in zip(jax.tree_util.tree_leaves(committed),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the original snapshot materials were never aliased/mutated
    for a, b in zip(jax.tree_util.tree_leaves(st0),
                    jax.tree_util.tree_leaves(snap_tree)):
        np.testing.assert_array_equal(np.asarray(a), b)


def test_cache_spec_snapshot_commit_unit():
    """Bare-cache unit: per-slot span gather + suffix restore at mixed
    depths and mixed keep counts."""
    from repro.serve.kvcache import cache_init, cache_update
    c = cache_init(2, 8, 1, 2, jnp.float32, per_slot=True)
    c = c._replace(pos=jnp.array([1, 3], jnp.int32))
    snap = cache_spec_snapshot(c, 3)
    k_new = jnp.arange(12, dtype=jnp.float32).reshape(2, 3, 1, 2) + 1
    c2 = cache_update(c, k_new, 2 * k_new)
    assert c2.pos.tolist() == [4, 6]
    c3 = cache_spec_commit(c2, snap, jnp.array([2, 0], jnp.int32))
    assert c3.pos.tolist() == [3, 3]
    # row 0 keeps its first 2 written tokens, the third is rolled back to 0
    np.testing.assert_array_equal(np.asarray(c3.k[0, 1:3]),
                                  np.asarray(k_new[0, :2]))
    assert float(jnp.abs(c3.k[0, 3]).max()) == 0.0
    # row 1 rolled back entirely: bytes bit-equal to pre-write state
    np.testing.assert_array_equal(np.asarray(c3.k[1]), np.asarray(c.k[1]))


# --- engine-level invariants --------------------------------------------------


def test_spec_engine_self_draft_accepts_everything():
    """draft == target (two pools over one runtime): every proposal matches
    the target distribution exactly, so at temperature 0 every draft is
    accepted and accept_rate is exactly 1.0."""
    cfg, rt = _rnn_runtime()
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4, draft=rt, spec_k=3)
    reqs = [Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab,
                    max_tokens=9, temperature=0.0, top_k=0, seed=7, rid=0)]
    _, m = eng.run(reqs, realtime=False)
    assert m["accept_rate"] == 1.0
    assert m["spec_traces"] == 1
    # an fp draft's step runs no Pallas kernel (a packed one on the TPU: 1)
    assert m["draft_launches"] == 0
    # 1 admit token + ceil(8 / (k+1)) fully-accepted rounds
    assert m["spec_rounds"] == 2


def test_spec_engine_gates_unsupported_runtimes():
    """Ring caches (gemma3 local layers) and hybrid SSMs (zamba2) cannot
    roll back a rejected suffix exactly — the engine must refuse upfront,
    not corrupt streams at runtime."""
    for arch in ("gemma3-27b", "zamba2-1.2b"):
        cfg = get_config(arch).reduced()
        rt = TransformerRuntime(cfg, T.model_init(jax.random.PRNGKey(0), cfg))
        assert not rt.spec_capable
        with pytest.raises(NotImplementedError, match="speculative"):
            ServeEngine(rt, cfg.vocab, slots=2, max_context=16,
                        draft=rt, spec_k=2)
    cfg, rt = _rnn_runtime()
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(rt, cfg.vocab, slots=2, max_context=16, draft=rt)
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(rt, cfg.vocab, slots=2, max_context=16, spec_k=2)
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(rt, cfg.vocab, slots=2, max_context=16, draft=rt,
                    spec_k=-1)
    with pytest.raises(ValueError, match="draft span"):
        # a verify's quota overshoot must stay inside the caches'
        # DECODE_MARGIN slack, or the non-ring clamp could alias writes
        ServeEngine(rt, cfg.vocab, slots=2, max_context=16, draft=rt,
                    spec_k=65)


def test_speculative_draft_requires_fp_masters():
    _, rt = _rnn_runtime(packed=True)
    with pytest.raises(ValueError, match="packed"):
        speculative_draft(rt)


def test_spec_engine_warm_then_run_traces_nothing_new():
    """Both lanes share each slot-surgery region: a speculative engine warms
    exactly the prefill traces a plain engine over the same runtime does
    (one per declared bucket), and cancel/admit/retire churn — with a
    prefix cache splicing both lanes in one row copy — retraces nothing."""
    cfg, rt = _rnn_runtime()
    draft = speculative_draft(rt, mode="ternary")
    eng = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                      prefill_chunk=4, draft=draft, spec_k=2,
                      prefix_cache=PrefixCache(1 << 24))
    eng.warm()
    pt, st = eng.prefill_traces, eng.spec_traces
    assert st == 1 and pt == len(eng.declared_buckets())
    plain = ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                        prefill_chunk=4)
    plain.warm()
    assert plain.prefill_traces == pt, "the draft lane traced its own prefill"
    rng = np.random.default_rng(11)
    system = rng.integers(0, cfg.vocab, size=8)
    reqs = [Request(prompt=np.concatenate(
                        [system, rng.integers(0, cfg.vocab,
                                              size=int(rng.integers(1, 5)))]),
                    max_tokens=int(rng.integers(1, 8)), temperature=0.0,
                    top_k=0, seed=300 + i, rid=i) for i in range(5)]
    comps, m = eng.run(reqs, realtime=False)
    assert len(comps) == len(reqs)
    # churn: cancel one request mid-prefill and one mid-decode, admit more
    for i in range(5, 9):
        eng.submit(dataclasses.replace(reqs[i - 5], rid=i, max_tokens=7))
    eng.step()
    assert eng._prefill_q, "the second admission's chunk has not run yet"
    prefilling = eng._active[eng._prefill_q[0]].rid
    assert eng.cancel(prefilling).finished == "cancelled"
    while not eng._live_host.any():
        eng.step()
    decoding = next(a.rid for s, a in enumerate(eng._active)
                    if eng._live_host[s])
    assert eng.cancel(decoding).finished == "cancelled"
    while eng.has_work():
        eng.step()
    assert eng.stats()["prefix_cache"]["hits"] > 0
    assert eng.prefill_traces == pt, "a prompt length traced a new prefill"
    assert eng.spec_traces == 1, "occupancy churn retraced the spec tick"
    assert eng.splice_traces == 1, "a splice retraced the lanes' row copy"


@pytest.mark.parametrize("max_tokens", [(12,), (12, 1, 7)])
def test_spec_step_returns_one_event_per_request(max_tokens):
    """A speculative step in which a request's last prompt chunk lands runs
    a round over it too: the first token and the round's ids are one event
    for that request, not two, and the concatenated events are exactly
    run()'s completion (a quota of one ends the request at its first
    token)."""
    cfg, rt = _rnn_runtime()
    draft = speculative_draft(rt, mode="ternary")

    def engine():
        return ServeEngine(rt, cfg.vocab, slots=2, max_context=64,
                           prefill_chunk=4, draft=draft, spec_k=2)

    reqs = [Request(prompt=np.arange(5 + 4 * i, dtype=np.int32) % cfg.vocab,
                    max_tokens=n, temperature=0.8, top_k=0, seed=40 + i,
                    rid=i) for i, n in enumerate(max_tokens)]
    comps, _ = engine().run([dataclasses.replace(r) for r in reqs],
                            realtime=False)
    want = {c.rid: c.tokens for c in comps}
    eng = engine()
    for r in reqs:
        eng.submit(dataclasses.replace(r))
    got = {r.rid: [] for r in reqs}
    shared = 0
    while eng.has_work():
        head = eng._active[eng._prefill_q[0]] if eng._prefill_q else None
        landing = head.rid if head is not None and len(head.chunks) == 1 \
            else None
        ticks = eng.ticks
        events, _ = eng.step()
        rids = [rid for rid, _ in events]
        assert len(rids) == len(set(rids)), f"two events for a rid: {events}"
        if landing is not None and eng.ticks > ticks:
            shared += 1
            toks = dict(events)[landing]
            assert len(toks) >= min(2, reqs[landing].max_tokens)
        for rid, toks in events:
            got[rid].extend(toks)
    assert shared == len(reqs), "every last chunk shares its step with a round"
    assert got == want
