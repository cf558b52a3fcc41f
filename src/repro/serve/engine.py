"""Continuous-batching serve engine (DESIGN.md §7-§10).

`ServeEngine` owns a fixed pool of B slots over any serving runtime
(BN-LSTM/GRU, RWKV6, Mamba2-hybrid, attention archs) and turns the lockstep
prefill→decode loop into mixed-length traffic serving:

  * requests are ADMITTED from a queue as slots free up — admission is pure
    bookkeeping: the prompt is split into fixed-size, bucket-padded CHUNKS
    and the slot enters a `prefilling` phase;
  * each scheduler iteration runs AT MOST ONE prefill chunk, straight into
    the admitted slot (gather the slot row, run the resumable chunk, write
    the row back), interleaved with the batched decode tick — a long prompt
    can never stall live decodes for more than one chunk's worth of work
    (Sarathi/SplitFuse-style, adapted to the mask-don't-reshape pool);
  * every tick runs ONE batched `decode_step` across all B slots with dead
    slots MASKED, never resliced — the tick's operand shapes are
    occupancy-independent, so jit traces the decode path exactly once and
    admit/retire between ticks cannot retrace it (asserted in tests);
    prefilling slots are dead for the tick, and the runtimes freeze dead
    rows' state bit-for-bit (a dead row may be mid-prefill);
  * a request's FIRST token is sampled when its last chunk lands —
    `Completion.t_first` is the real first-token time, not the admission
    time — then the slot turns live and decodes;
  * slots RETIRE on EOS or per-request max-tokens and are immediately
    reusable; freed slots are scrubbed in one batched shape-aware reset
    (recurrent leaves and positions to zero, attention KV masked in place)
    because the next occupant's prefill RESUMES from the slot row.

The scheduler is driven through a RESUMABLE step API (DESIGN.md §10):
`submit()` enqueues a request (priority/SLO-ordered admission), `step()`
runs ONE scheduler iteration and returns the tokens it sampled plus any
completions, and `cancel(rid)` retires an in-flight request mid-stream —
mid-prefill or mid-decode — through the same batched scrub retirement
uses, so a hung-up client leaks nothing into the slot's next occupant.
`run()` is a thin loop over submit/step (byte-identical to the pre-step-API
batch driver); the asyncio front door (serve/frontdoor.py) drives step()
from an event loop while requests arrive and die asynchronously.

With a `PrefixCache` (serve/prefixcache.py) attached, admission splices the
longest cached prompt prefix straight into the slot instead of re-prefilling
it — for the RNN family that is ONE (L, H) row-pair copy, the O(1)-state
advantage the paper's hardware pitch implies — and every full prefill chunk
that lands offers the carried slot state back to the cache at its
chunk-boundary offset.

Sampling is per-slot vectorized (serve/sampler.sample_slots): each slot
carries its own temperature / top-k / PRNG key chain, and a slot's draws are
bit-identical to running that request alone through `drive_session` — the
engine changes the schedule, not the tokens.

Speculative decoding (DESIGN.md §9) replaces the tick with a
draft-verify-accept round: a packed binary/ternary DRAFT runtime (its own
slot pool, prefilled and scrubbed in lockstep) proposes `spec_k` tokens per
live slot, the target verifies them all in one multi-token step, and
rejection sampling commits each slot's accepted prefix — the output
distribution is exactly the target's, byte-identical to plain decoding at
temperature 0.  Rollback of rejected suffixes reuses the slot surgery:
per-step state SELECT for RNN families, KV suffix byte-restore + pos
rewind for attention.

Every jitted region takes the runtime's parameter tree as an ARGUMENT
(`rt.jit_prm`) instead of closing over it: closed-over weights get
constant-folded, which shifts logits ~1ulp against the arg-passed
`drive_session` jits and makes logits-level comparisons unsound.  Passing
the same pytree every call leaves the trace count at 1 (asserted lifelong).
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import telemetry
from repro.serve.sampler import sample_slots, spec_accept

Array = jax.Array


@dataclasses.dataclass
class Request:
    """One generation request.  `arrival_s` is the arrival time relative
    to engine start — the traffic replay sets it from a Poisson process;
    latency is measured against it.  None (a live client's request) means
    it arrives when `submit()` is called, and is timed from that stamp.

    `priority` orders ADMISSION (lower = admitted sooner; ties fall back to
    arrival time, then submit order).  Admission is preemption-free: a
    running low-priority request is never evicted, a queued one is only
    overtaken.  `slo` is a reporting label — per-class TTFT percentiles are
    broken out in the run metrics so deadline classes can be provisioned
    separately."""

    prompt: Any                  # (S,) int token ids (list / np / jnp)
    max_tokens: int
    temperature: float = 0.8
    top_k: int = 0
    seed: int = 0
    arrival_s: Optional[float] = None
    priority: int = 0
    slo: str = "default"
    rid: Optional[int] = None    # engine numbers submissions when None (the
                                 # Request object itself is never mutated)


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: List[int]            # sampled ids, EOS included when hit
    prompt_len: int
    finished: str                # 'length' | 'eos' | 'cancelled'
    slot: int
    t_submit: float              # engine-relative seconds
    t_admit: float               # slot allocated; prefill starts after this
    t_first: float               # the FIRST token was actually sampled (the
                                 # prompt's last chunk landed) — real TTFT
    t_done: float
    cached_tokens: int = 0       # prompt tokens a prefix-cache splice skipped
    slo: str = "default"

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def queue_s(self) -> float:
        return self.t_admit - self.t_submit

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_submit


@dataclasses.dataclass
class _Active:
    req: Request
    rid: int            # kept here so the caller's Request is never mutated
    tokens: List[int]
    t_submit: float
    t_admit: float
    t_first: Optional[float]            # stamped when the first token samples
    chunks: Deque[Tuple[np.ndarray, int]]  # remaining (padded chunk, n real)
    prompt: np.ndarray  # the full prompt ids (prefix-cache keys slice it)
    off: int            # real prompt tokens consumed so far (incl. spliced)
    cached: int         # tokens a prefix-cache splice made unnecessary


# ---------------------------------------------------------------------------
# generic slot surgery over state pytrees
# ---------------------------------------------------------------------------


def tree_write_slot(pool, sub, slot):
    """Insert a batch-1 state pytree into row `slot` of every pool leaf.

    Works for any state the runtimes produce — stacked or tail
    AttnCache/SSMState/RWKVState nodes and bare array leaves alike — by
    delegating AttnCache nodes to `kvcache.cache_write_slot` (the one
    attention-cache insert implementation) and everything else to
    `kvcache.write_row`, which recovers the slot axis per leaf from the
    static shapes.  `slot` itself is traced, so one compilation serves
    every admission."""
    from repro.serve.kvcache import AttnCache, cache_write_slot, write_row

    is_cache = lambda x: isinstance(x, AttnCache)
    return jax.tree.map(
        lambda p, s: (cache_write_slot(p, s, slot) if is_cache(p)
                      else write_row(p, s, slot)),
        pool, sub, is_leaf=is_cache)


def tree_gather_slot(pool, ref, slot):
    """Read row `slot` of every pool leaf as a batch-1 state pytree — the
    exact inverse of `tree_write_slot`, and the read half of in-slot chunked
    prefill (gather the slot, run one chunk, write it back).  `ref` is a
    batch-1 template of the pool (arrays or ShapeDtypeStructs); its static
    shapes recover the slot axis per leaf."""
    from repro.serve.kvcache import AttnCache, cache_gather_slot, read_row

    is_cache = lambda x: isinstance(x, AttnCache)
    return jax.tree.map(
        lambda p, r: (cache_gather_slot(p, r, slot) if is_cache(p)
                      else read_row(p, r.shape, slot)),
        pool, ref, is_leaf=is_cache)


def tree_reset_slots(pool, ref, mask):
    """Scrub slots where `mask` (B,) is True, shape-aware via the batch-1
    template `ref`: recurrent leaves (h/c, S-matrices, conv tails, shift
    buffers) and every position counter drop to ZERO along the recovered
    slot axis; AttnCache nodes keep their KV bytes and reset only pos
    (stale entries read as unwritten — mask-don't-reshape).  A freed slot
    must read exactly like a fresh one: the next occupant's chunked prefill
    RESUMES from the slot row."""
    from repro.serve.kvcache import (AttnCache, _slot_axis, cache_reset_slots)

    is_cache = lambda x: isinstance(x, AttnCache)

    def scrub(p, r):
        if is_cache(p):
            return cache_reset_slots(p, mask)
        ax = _slot_axis(p.shape, r.shape)
        z = jnp.zeros((), p.dtype)
        if ax is None:  # 1-slot pool: the whole leaf belongs to slot 0
            return jnp.where(mask[0], z, p)
        m = mask.reshape((1,) * ax + (-1,) + (1,) * (p.ndim - ax - 1))
        return jnp.where(m, z, p)

    return jax.tree.map(scrub, pool, ref, is_leaf=is_cache)


def _bucket(n: int, cap: int) -> int:
    """Smallest power-of-two >= n, capped at the chunk size — the static
    prefill shapes, so trace count is O(log chunk), not O(#prompt lengths)."""
    b = 1
    while b < n and b < cap:
        b <<= 1
    return min(b, cap)


def _arrival(req: Request) -> float:
    # a request with no arrival time of its own orders as already queued
    return 0.0 if req.arrival_s is None else req.arrival_s


def _origin(req: Request, t_submit: float) -> float:
    # what its latencies are timed from: the arrival, else the submit stamp
    return t_submit if req.arrival_s is None else req.arrival_s


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Slotted continuous-batching scheduler over one serving runtime.

    eng = ServeEngine(rt, vocab, slots=8, max_context=512, prefill_chunk=32)
    completions, metrics = eng.run(requests)

    or, resumably (the front door's driving mode):

    rid = eng.submit(Request(...))
    while eng.has_work():
        token_events, completions = eng.step()   # [(rid, [ids])], [Completion]
    eng.cancel(rid)                              # any time, any phase

    Speculative mode (DESIGN.md §9) pairs the target with a packed draft:

    eng = ServeEngine(rt, vocab, slots=8, max_context=512,
                      draft=speculative_draft(rt), spec_k=4)

    Mesh mode (DESIGN.md §12) scales the same engine across devices —
    slot pool data-parallel (N× slots, one tick per mesh), weights
    tensor-parallel per the runtime's serving rules:

    eng = ServeEngine(rt, vocab, slots=32, max_context=512,
                      mesh=make_serve_mesh("data=4,model=2"))

    Invariants (DESIGN.md §7-§10, §12):
      * mask-don't-reshape — the pool state, the token/key/temperature
        arrays and therefore the jitted tick keep shape (B, ...) forever;
        occupancy lives in a boolean mask;
      * one trace — `tick_traces` counts jit traces of the decode tick and
        stays at 1 across arbitrary submit/cancel/admit/retire
        interleavings (in spec mode `spec_traces` counts the
        draft-verify-accept round the same way); `prefill_traces` counts
        chunk-prefill traces and is bounded by the declared bucket set
        (warm() compiles them all up front); `splice_traces` counts the
        prefix-cache row-copy and stays at 1 (splices run at full pool-row
        shape);
      * no head-of-line blocking — at most ONE prefill chunk runs between
        decode ticks, so an admission never stalls live decodes for more
        than one chunk of work (`max_decode_stall_ticks` <= 1);
      * per-request determinism — a request's token stream depends only on
        (prompt, seed, sampling params), never on which slot it landed in,
        what shared the batch, how its prompt was chunked, whether a
        prefix-cache splice skipped part of it, or which neighbours were
        cancelled mid-flight.
    """

    def __init__(self, rt, vocab: int, *, slots: int, max_context: int,
                 eos_id: Optional[int] = None, prefill_chunk: int = 32,
                 draft=None, spec_k: int = 0, prefix_cache=None, mesh=None):
        if slots < 1:
            raise ValueError("need at least one slot")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if getattr(rt, "extras", None):
            raise NotImplementedError(
                "continuous batching over cross-attention runtimes (vlm/"
                "audio) needs per-request source encodings; the engine "
                "currently schedules self-attention and recurrent archs")
        if (draft is None) != (spec_k == 0) or spec_k < 0:
            raise ValueError("speculative mode needs BOTH a draft runtime "
                             "and spec_k >= 1 (got draft="
                             f"{'set' if draft is not None else 'None'}, "
                             f"spec_k={spec_k})")
        if spec_k > 64:
            # a verify may overshoot a slot's quota by up to spec_k cache
            # writes; attention pools carry DECODE_MARGIN (128) slack
            # columns past max_context, and staying well inside it keeps
            # the non-ring write clamp from ever aliasing a LIVE row
            raise ValueError(f"spec_k={spec_k} is past the supported draft "
                             "span (64); deep speculation gains nothing — "
                             "acceptance decays geometrically")
        if draft is not None:
            if not (getattr(rt, "spec_capable", False)
                    and getattr(draft, "spec_capable", False)):
                raise NotImplementedError(
                    "speculative decoding needs an exactly-rollbackable "
                    "multi-token step on both runtimes: RNN families and "
                    "pure-attention non-ring archs qualify; ring-cache, "
                    "MoE and rwkv/mamba runtimes do not (DESIGN.md §9)")
            if getattr(draft, "family", None) != getattr(rt, "family", None):
                raise ValueError("draft and target must be the same serving "
                                 "family — self-speculation pairs a packed "
                                 "export with its own fp masters")
        self.rt = rt
        self.vocab = int(vocab)
        self.n_slots = int(slots)
        self.max_context = int(max_context)
        self.eos_id = eos_id
        self.prefill_chunk = int(prefill_chunk)
        # how the runtime lets prompts be split (serve/recurrent.py):
        # 'token' granularity chunks anywhere; 'whole' archs (MoE capacity
        # competition, rwkv/mamba internal scan chunking) prefill the prompt
        # as one in-slot chunk.  pad_buckets = padded tails are exact.
        self._granularity = getattr(rt, "chunk_granularity", "whole")
        self._pad = bool(getattr(rt, "pad_buckets", False))

        # prefix-state caching (DESIGN.md §10): boundaries are exact
        # carried-state offsets only under token-granularity chunking, and
        # the narrowed attention snapshot assumes non-ring caches (a ring's
        # live window need not start at column 0)
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if not (self._granularity == "token"
                    and (getattr(rt, "family", None) == "rnn" or self._pad)):
                raise NotImplementedError(
                    "prefix-state caching needs token-granularity chunked "
                    "prefill and (for attention archs) non-ring caches — "
                    "the §8 bit-exact chunk-boundary contract is what makes "
                    "a spliced prefix byte-identical to re-prefilling it")
            prefix_cache.bind(self.prefill_chunk)

        self.pool = rt.init_state(self.n_slots, self.max_context,
                                  per_slot=True)
        # batch-1 template: fixes the slot axis of every pool leaf for the
        # gather/reset surgery (shapes only — no arrays are materialized)
        self._ref = jax.eval_shape(
            lambda: rt.init_state(1, self.max_context, per_slot=True))
        # the parameter trees every jitted region takes as ARGUMENTS (see
        # module docstring: closing over them constant-folds the weights)
        self._prm = rt.jit_prm
        # speculative mode (DESIGN.md §9): the packed draft runs its OWN
        # slot pool in lockstep with the target's — admission prefills
        # both, retirement scrubs both, and the spec tick rolls both back
        # to the accepted prefix
        self.draft = draft
        self.spec_k = int(spec_k)
        self.spec = draft is not None
        if self.spec:
            self.draft_pool = draft.init_state(self.n_slots,
                                               self.max_context,
                                               per_slot=True)
            self._dref = jax.eval_shape(
                lambda: draft.init_state(1, self.max_context, per_slot=True))
            self._dprm = draft.jit_prm
        B = self.n_slots
        self._pending = jnp.zeros((B,), jnp.int32)   # next token to feed
        self._live = jnp.zeros((B,), bool)
        self._keys = jnp.zeros((B, 2), jnp.uint32)   # per-slot PRNG chain
        self._temp = jnp.ones((B,), jnp.float32)
        self._topk = jnp.zeros((B,), jnp.int32)
        self._live_host = np.zeros(B, bool)
        self._active: List[Optional[_Active]] = [None] * B
        self._prefill_q: Deque[int] = deque()   # slots mid-prefill, FIFO
        self._rid = 0
        # the admission queue: a priority heap of submitted-but-unadmitted
        # requests ordered (priority, arrival_s, submit seq), each carrying
        # its submit stamp.  Cancellation of a queued request is lazy: the
        # rid goes into `_cancel_pending` and the entry is dropped when it
        # reaches the heap top.
        self._heap: List[Tuple[int, float, int, int, Request, float]] = []
        self._seq = 0
        self._queued_rids: Set[int] = set()
        self._cancel_pending: Set[int] = set()
        self._t0 = time.perf_counter()
        self._compiles0 = telemetry.compiles()

        self.ticks = 0
        self.tick_traces = 0      # python counters bumped at TRACE time only
        self.prefill_traces = 0
        self.spec_traces = 0
        self.splice_traces = 0
        # Pallas launches the decode tick dispatches per call, measured the
        # same way tick_traces is: the kernel wrappers bump a trace-time
        # counter (kernels/dispatch.py) and the tick body diffs it while
        # being traced.  1 on the packed whole-tick path, 0 on the CPU
        # dense-fallback path (no interpret-mode Pallas in serving), -1
        # until the first tick traces.
        self.tick_launches = -1
        # the same count for the speculative draft's decode step inside
        # the spec round's scan (1 for a packed draft's fused tick)
        self.draft_launches = -1
        self._occupancy_sum = 0.0
        self._gen_tokens = 0      # cumulative over the engine's life
        self._drafted = 0         # speculative accounting: proposed drafts
        self._accepted = 0        # ... and how many of them survived verify
        # decode-stall accounting: chunks an admission ran since the last
        # decode tick while live decodes were waiting.  The scheduler's
        # contract is that this never exceeds ONE chunk per admission.
        self._stall_pending: Dict[int, int] = {}
        self._stall_max = 0

        # -- mesh placement (DESIGN.md §12) ---------------------------------
        # A mesh-native engine shards the slot pool over the mesh's data
        # axes (slot s lives on shard s // (slots/D)) and the weights
        # tensor-parallel over 'model' per the runtime's serving rules,
        # then pins every jitted region's in/out shardings so the layouts
        # are part of the ONE trace — admit/retire/splice between ticks
        # can never force a reshard, and tick_traces==1 holds per mesh
        # exactly as it does per device.
        self.mesh = mesh
        self._data_shards = 1
        self._pool_sh = self._dpool_sh = None
        self._sub_sh = self._dsub_sh = None
        self._prm_sh = self._dprm_sh = None
        self._vec_sh = self._row_sh = self._rep = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.kernels import dispatch
            from repro.launch.sharding import (batch_shardings,
                                               serve_pool_shardings)
            if dispatch.packed_pallas_active(
                    (self._prm, self._dprm if self.spec else None)):
                raise NotImplementedError(
                    "mesh-sharded serving of packed trees runs through the "
                    "compiled dense fallback (CPU) — the packed Pallas "
                    "kernels are single-device launches; their shard_map "
                    "port is the ROADMAP item")
            daxes = [a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1]
            D = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
            if self.n_slots % D:
                raise ValueError(
                    f"slots={self.n_slots} must split evenly over the "
                    f"mesh's {D} data shard(s) — the slot pool shards "
                    f"along 'data'")
            self._data_shards = D
            self._rep = NamedSharding(mesh, P())
            self._vec_sh, self._row_sh = batch_shardings(
                (self._pending, self._keys), mesh)
            self._pool_sh = serve_pool_shardings(self.pool, self._ref, mesh)
            self._sub_sh = jax.tree.map(lambda _: self._rep, self._pool_sh)
            self._prm_sh = rt.serve_prm_shardings(mesh)
            self.pool = jax.device_put(self.pool, self._pool_sh)
            self._prm = jax.device_put(self._prm, self._prm_sh)
            self._pending = jax.device_put(self._pending, self._vec_sh)
            self._live = jax.device_put(self._live, self._vec_sh)
            self._keys = jax.device_put(self._keys, self._row_sh)
            self._temp = jax.device_put(self._temp, self._vec_sh)
            self._topk = jax.device_put(self._topk, self._vec_sh)
            if self.spec:
                self._dpool_sh = serve_pool_shardings(
                    self.draft_pool, self._dref, mesh)
                self._dsub_sh = jax.tree.map(lambda _: self._rep,
                                             self._dpool_sh)
                self._dprm_sh = draft.serve_prm_shardings(mesh)
                self.draft_pool = jax.device_put(self.draft_pool,
                                                 self._dpool_sh)
                self._dprm = jax.device_put(self._dprm, self._dprm_sh)

        def _mjit(fn, in_sh=None, out_sh=None, donate=()):
            # sharding-annotated jit for the mesh-native engine; the
            # mesh=None engine compiles exactly as before.  Pinning BOTH
            # sides means host-built operands (chunk tokens, slot indices,
            # reset masks, fresh PRNG keys) are placed on entry and every
            # result lands already laid out for the next region.
            if mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                           donate_argnums=donate)

        def tick(prm, pool, pending, live, keys, temp, topk):
            self.tick_traces += 1
            from repro.kernels import dispatch
            launches0 = dispatch.launch_count()
            logits, pool = rt.decode_fn(pending, pool, live, prm=prm)
            self.tick_launches = dispatch.launch_count() - launches0
            ks = jax.vmap(jax.random.split)(keys)    # (B, 2, 2)
            nxt = sample_slots(logits, ks[:, 1], temperature=temp,
                               top_k=topk, vocab=self.vocab)
            # dead slots: freeze the key chain and keep feeding the same
            # token, so a zombie slot's arrays are time-invariant
            nxt = jnp.where(live, nxt, pending)
            keys = jnp.where(live[:, None], ks[:, 0], keys)
            return pool, nxt, keys

        # the pool is dead the moment the tick/prefill/reset returns its
        # successor, so donate it (and the pending/key chains) — without
        # donation every tick would COPY all B KV caches.  Every backend
        # donates (the CPU too), so the tests run the chip's buffer
        # discipline.  The prm tree is NEVER donated: the same arrays are
        # passed every call.
        self._tick = _mjit(
            tick,
            in_sh=(self._prm_sh, self._pool_sh, self._vec_sh, self._vec_sh,
                   self._row_sh, self._vec_sh, self._vec_sh),
            out_sh=(self._pool_sh, self._vec_sh, self._row_sh),
            donate=(1, 2, 4))

        def admit_commit(logits, key, t, k, pending, keys, temp, topk, live,
                         slot):
            # the request's first token: same key discipline as the
            # sequential loop (split once, sample with the second half) —
            # plus ALL the slot-array writes the admission needs, in ONE
            # dispatch (five eager at[].set calls used to dominate the
            # admission cost on CPU)
            ks = jax.random.split(key)
            tok = sample_slots(logits, ks[1][None], temperature=t[None],
                               top_k=k[None], vocab=self.vocab)[0]
            return (tok, pending.at[slot].set(tok), keys.at[slot].set(ks[0]),
                    temp.at[slot].set(t), topk.at[slot].set(k),
                    live.at[slot].set(True))

        R = self._rep
        self._admit_commit = _mjit(
            admit_commit,
            in_sh=(R, R, R, R, self._vec_sh, self._row_sh, self._vec_sh,
                   self._vec_sh, self._vec_sh, R),
            out_sh=(R, self._vec_sh, self._row_sh, self._vec_sh,
                    self._vec_sh, self._vec_sh))

        write = rt.write_slots if hasattr(rt, "write_slots") else tree_write_slot

        def prefill_slot(prm, pool, tokens, n, slot):
            # in-slot chunked prefill: the slot row IS the session state.
            # Retraces once per bucket length (tokens' static shape); slot
            # and n are traced, so one trace serves every admission.
            self.prefill_traces += 1
            sub = tree_gather_slot(pool, self._ref, slot)
            logits, sub = rt.prefill_chunk(tokens, sub, n, prm=prm)
            return logits, write(pool, sub, slot)

        self._prefill_slot = _mjit(
            prefill_slot,
            in_sh=(self._prm_sh, self._pool_sh, R, R, R),
            out_sh=(R, self._pool_sh),
            donate=(1,))
        # retire-time slot scrub, shape-aware: recurrent leaves + positions
        # to zero, attention KV masked in place, the device live bit
        # cleared — the freed row must read as fresh because the next
        # prefill resumes from it
        self._reset = _mjit(
            lambda pool, live, mask: (
                tree_reset_slots(pool, self._ref, mask),
                jnp.where(mask, False, live)),
            in_sh=(self._pool_sh, self._vec_sh, self._vec_sh),
            out_sh=(self._pool_sh, self._vec_sh),
            donate=(0,))

        if self.prefix_cache is not None:
            # prefix-cache device paths.  The splice is the SAME full-row
            # write admission prefill uses (entries are widened to the pool
            # row shape outside jit), so it traces exactly once; the gather
            # reads the slot row for snapshotting without donating the pool.
            self._gather = _mjit(
                lambda pool, slot: tree_gather_slot(pool, self._ref, slot),
                in_sh=(self._pool_sh, R), out_sh=self._sub_sh)

            def splice(pool, sub, slot):
                self.splice_traces += 1
                return write(pool, sub, slot)

            self._splice = _mjit(
                splice, in_sh=(self._pool_sh, self._sub_sh, R),
                out_sh=self._pool_sh, donate=(0,))

        if not self.spec:
            return

        # -- speculative mode: draft k, verify k+1, accept, commit ----------
        K = self.spec_k

        def spec_tick(prm, dprm, pool, dpool, pending, live, keys, temp,
                      topk):
            """One draft-verify-accept round over ALL live slots, jitted as
            a unit (traces exactly once — asserted like the plain tick):

              1. the packed draft proposes K tokens per slot: a scan of
                 K+1 batched draft decode steps (the last one advances the
                 draft through its own K-th proposal so a fully-accepted
                 round leaves the draft in sync), sampling proposals with
                 each slot's own temperature/top-k;
              2. the target verifies all candidates in ONE multi-token
                 step — `rt.verify` returns logits at every position;
              3. `spec_accept` runs the rejection rule per slot: the
                 output distribution is exactly the target's, and at
                 temperature 0 the emitted bytes are plain greedy decode;
              4. both pools COMMIT to each slot's accepted prefix:
                 per-step-state select for RNN families, KV suffix
                 restore + pos rewind for attention (the PR 3/4 slot
                 surgery, turned into a rollback primitive).

            Dead slots (empty or mid-prefill) stay bit-frozen: their
            decode rows are masked, their accepted count is forced to 0
            (commit restores their pre-round state exactly), and their
            pending/key chains never advance."""
            self.spec_traces += 1
            from repro.kernels import dispatch
            ks = jax.vmap(jax.random.split)(keys)          # (B, 2, 2)
            rk = ks[:, 1]
            new_keys = jnp.where(live[:, None], ks[:, 0], keys)
            dkeys = jax.vmap(
                lambda k: jax.random.split(jax.random.fold_in(k, 1),
                                           K + 1))(rk)     # (B, K+1, 2)
            akeys = jax.vmap(jax.random.fold_in,
                             in_axes=(0, None))(rk, 2)     # (B, 2)

            dsnap = draft.spec_snapshot(dpool, K + 1)

            def dbody(carry, step_keys):
                dst, tok = carry
                launches0 = dispatch.launch_count()
                lg, dst = draft.decode_fn(tok, dst, live, prm=dprm)
                self.draft_launches = dispatch.launch_count() - launches0
                nxt = sample_slots(lg, step_keys, temperature=temp,
                                   top_k=topk, vocab=self.vocab)
                nxt = jnp.where(live, nxt, tok)
                return (dst, nxt), (lg, nxt, draft.spec_emit(dst))

            (dafter, _), (qlg, dtoks, demits) = jax.lax.scan(
                dbody, (dpool, pending), jnp.swapaxes(dkeys, 0, 1))
            drafts = jnp.swapaxes(dtoks[:K], 0, 1)         # (B, K)
            q_logits = jnp.swapaxes(qlg[:K], 0, 1)         # (B, K, V)

            vtokens = jnp.concatenate([pending[:, None], drafts], axis=1)
            vsnap = rt.spec_snapshot(pool, K + 1)
            p_logits, vafter, vemits = rt.verify(vtokens, pool, live,
                                                 prm=prm)

            n_acc, out = spec_accept(p_logits, q_logits, drafts, akeys,
                                     temperature=temp, top_k=topk,
                                     vocab=self.vocab)
            n_acc = jnp.where(live, n_acc, 0)
            pool = rt.spec_commit(pool, vafter, vsnap, vemits, n_acc)
            dpool = draft.spec_commit(dpool, dafter, dsnap, demits, n_acc)
            nxt_p = jnp.take_along_axis(
                out, jnp.maximum(n_acc - 1, 0)[:, None], axis=1)[:, 0]
            pending = jnp.where(live, nxt_p, pending)
            # ONE host-bound array per round: emitted tokens with the
            # accepted count in the last column (a second small transfer
            # costs as much as the whole verify at reduced scale)
            packed = jnp.concatenate([out, n_acc[:, None]], axis=1)
            return pool, dpool, pending, new_keys, packed

        self._spec_tick = _mjit(
            spec_tick,
            in_sh=(self._prm_sh, self._dprm_sh, self._pool_sh,
                   self._dpool_sh, self._vec_sh, self._vec_sh, self._row_sh,
                   self._vec_sh, self._vec_sh),
            out_sh=(self._pool_sh, self._dpool_sh, self._vec_sh,
                    self._row_sh, self._row_sh),
            donate=(2, 3, 4, 6))

        dwrite = (draft.write_slots if hasattr(draft, "write_slots")
                  else tree_write_slot)

        def spec_prefill_slot(prm, dprm, pool, dpool, tokens, n, slot):
            # same in-slot chunk as the plain path, run against BOTH pools
            # in one jitted region — the draft must carry the same prompt
            # state as the target or its proposals start from nowhere.
            # Trace-bounded by the same bucket set (one counter).
            self.prefill_traces += 1
            sub = tree_gather_slot(pool, self._ref, slot)
            logits, sub = rt.prefill_chunk(tokens, sub, n, prm=prm)
            dsub = tree_gather_slot(dpool, self._dref, slot)
            _, dsub = draft.prefill_chunk(tokens, dsub, n, prm=dprm)
            return (logits, write(pool, sub, slot),
                    dwrite(dpool, dsub, slot))

        self._spec_prefill_slot = _mjit(
            spec_prefill_slot,
            in_sh=(self._prm_sh, self._dprm_sh, self._pool_sh,
                   self._dpool_sh, R, R, R),
            out_sh=(R, self._pool_sh, self._dpool_sh),
            donate=(2, 3))
        self._spec_reset = _mjit(
            lambda pool, dpool, live, mask: (
                tree_reset_slots(pool, self._ref, mask),
                tree_reset_slots(dpool, self._dref, mask),
                jnp.where(mask, False, live)),
            in_sh=(self._pool_sh, self._dpool_sh, self._vec_sh,
                   self._vec_sh),
            out_sh=(self._pool_sh, self._dpool_sh, self._vec_sh),
            donate=(0, 1))

        if self.prefix_cache is not None:
            self._dgather = _mjit(
                lambda pool, slot: tree_gather_slot(pool, self._dref, slot),
                in_sh=(self._dpool_sh, R), out_sh=self._dsub_sh)

            def dsplice(dpool, dsub, slot):
                return dwrite(dpool, dsub, slot)

            self._dsplice = _mjit(
                dsplice, in_sh=(self._dpool_sh, self._dsub_sh, R),
                out_sh=self._dpool_sh, donate=(0,))

    # -- clock --------------------------------------------------------------

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    # -- admission ----------------------------------------------------------

    def _validate(self, req: Request) -> None:
        size = int(np.asarray(req.prompt).size)
        if size == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_tokens < 1:
            raise ValueError(f"request {req.rid}: max_tokens must be >= 1 "
                             f"(got {req.max_tokens}) — the last prompt "
                             f"chunk always samples the first token")
        if size + req.max_tokens > self.max_context:
            raise ValueError(
                f"request {req.rid}: needs {size}+{req.max_tokens} tokens; "
                f"engine provisioned max_context={self.max_context}")

    def _chunk_plan(self, size: int) -> List[Tuple[int, int]]:
        """Split a prompt of `size` tokens into (bucket_len, n_real) chunks.
        'token' granularity: full `prefill_chunk` chunks plus a tail,
        bucket-padded to a power of two when the runtime supports exact
        padding.  'whole' granularity: the prompt is one chunk."""
        C = self.prefill_chunk
        if self._granularity == "whole":
            return [(size, size)]
        plan = [(C, C)] * (size // C)
        r = size % C
        if r:
            plan.append((_bucket(r, C), r) if self._pad else (r, r))
        return plan

    def declared_buckets(self, prompt_lens: Sequence[int] = ()) -> List[int]:
        """The static chunk lengths `warm()` compiles.  Bucket-padding
        runtimes declare the traffic-independent power-of-two set — after
        warming it, NO workload can trace a new prefill shape.  Exact-length
        runtimes derive the set from the prompt lengths they are told about
        (plus the full chunk)."""
        bs = {1}  # warm()'s throwaway request prefills a 1-token prompt
        lens = {int(l) for l in prompt_lens if int(l) > 0}
        if self._granularity == "whole":
            bs |= lens
        elif self._pad:
            C = self.prefill_chunk
            bs.add(C)
            b = 1
            while b < C:
                bs.add(b)
                b <<= 1
        else:
            for l in lens:
                bs |= {Lb for Lb, _ in self._chunk_plan(l)}
        return sorted(bs)

    def warm(self, prompt_lens: Sequence[int] = ()) -> None:
        """Compile outside the measured run: one prefill trace per declared
        chunk bucket, plus the tick and the first-token sampler.  After
        this, a measured `run()` performs ZERO new traces (asserted in
        tests via the prefill_traces/tick_traces counters).  Shared by the
        --traffic launcher and the benchmark so both measure the same
        warmed serving path."""
        for Lb in self.declared_buckets(prompt_lens):
            if self.spec:
                _, self.pool, self.draft_pool = self._spec_prefill_slot(
                    self._prm, self._dprm, self.pool, self.draft_pool,
                    jnp.zeros((1, Lb), jnp.int32), jnp.int32(Lb),
                    jnp.int32(0))
            else:
                _, self.pool = self._prefill_slot(
                    self._prm, self.pool, jnp.zeros((1, Lb), jnp.int32),
                    jnp.int32(Lb), jnp.int32(0))
        # the warm prefills ran junk through slot 0 — scrub it so the pool
        # is indistinguishable from fresh before any real admission.  (They
        # ran OUTSIDE _prefill_step, so no junk prefix was offered to the
        # prefix cache either.)
        mask = np.zeros(self.n_slots, bool)
        mask[0] = True
        self._scrub(mask)
        # a throwaway request exercises admit + sample + the tick and
        # leaves every slot idle again; max_tokens respects tiny contexts
        n = min(2, self.max_context - 1)
        if n >= 1:
            self.run([Request(prompt=np.zeros(1, np.int32), max_tokens=n,
                              temperature=1.0, top_k=0, seed=0, rid=-1)],
                     realtime=False)

    def _free_slot(self) -> Optional[int]:
        # a slot is busy while PREFILLING too (live only after its first
        # token), so occupancy is "has an _Active", not the decode mask
        busy = np.array([a is not None for a in self._active])
        idle = np.flatnonzero(~busy)
        if not idle.size:
            return None
        if self._data_shards <= 1:
            return int(idle[0])
        # mesh: spread admissions over the data shards (slot s lives on
        # shard s // per — contiguous blocks, see serve_pool_shardings)
        # so a half-empty pool decodes on D shards instead of piling onto
        # shard 0.  Slot choice never affects a request's bytes (the §7
        # per-request determinism invariant), so balancing is free.
        per = self.n_slots // self._data_shards
        occ = busy.reshape(self._data_shards, per).sum(axis=1)
        return int(min(idle, key=lambda s: (occ[s // per], int(s))))

    # -- the resumable scheduling API (DESIGN.md §10) -----------------------

    def submit(self, req: Request) -> int:
        """Enqueue one request for admission.  Returns its rid — the handle
        `cancel` and the step events refer to.  Safe to call between any
        two `step()` calls; the request is admitted (in priority order) as
        soon as a slot frees."""
        self._validate(req)
        rid = self._rid if req.rid is None else req.rid
        self._rid = max(self._rid, rid) + 1
        heapq.heappush(self._heap, (req.priority, _arrival(req), self._seq,
                                    rid, req, self._now()))
        self._seq += 1
        self._queued_rids.add(rid)
        return rid

    def cancel(self, rid: int) -> Optional[Completion]:
        """Retire request `rid` wherever it is: queued (dropped before it
        ever touches a slot), mid-prefill, or mid-decode.  In-flight
        cancellation goes through the SAME batched shape-aware scrub as
        normal retirement — the freed slot reads exactly like a fresh one,
        so a hung-up client cannot leak state into the next occupant (and
        no new jit traces occur: the scrub is already compiled).

        Returns a Completion with finished='cancelled' carrying the tokens
        streamed so far, or None if the rid is unknown / already done."""
        for slot, act in enumerate(self._active):
            if act is not None and act.rid == rid:
                now = self._now()
                if slot in self._prefill_q:
                    self._prefill_q.remove(slot)
                    self._stall_pending.pop(rid, None)
                comp = self._completion(act, slot, now, finished="cancelled")
                self._retire(slot)
                mask = np.zeros(self.n_slots, bool)
                mask[slot] = True
                self._scrub(mask)
                return comp
        if rid in self._queued_rids:
            # lazy heap deletion: the entry is skipped when it surfaces
            self._queued_rids.discard(rid)
            self._cancel_pending.add(rid)
            req, t_sub = next((r, t) for (_, _, _, hr, r, t) in self._heap
                              if hr == rid)
            now = self._now()
            return Completion(
                rid=rid, tokens=[],
                prompt_len=int(np.asarray(req.prompt).size),
                finished="cancelled", slot=-1,
                t_submit=_origin(req, t_sub),
                t_admit=now, t_first=now, t_done=now, slo=req.slo)
        return None

    def has_work(self) -> bool:
        """True while a `step()` could make progress: queued, prefilling or
        decoding requests exist."""
        return bool(self._heap or self._prefill_q or self._live_host.any())

    def step(self) -> Tuple[List[Tuple[int, List[int]]], List[Completion]]:
        """ONE scheduler iteration: admit queued requests into free slots
        (priority order), run at most one prefill chunk, run one batched
        decode tick (or draft-verify-accept round), retire and scrub.

        Returns (token_events, completions): token_events is a list of
        (rid, [token ids sampled this iteration]) in stream order — one id
        per live slot per plain tick, up to spec_k+1 per spec round, the
        first token when a prompt's last chunk lands; completions are the
        requests that finished this iteration.  `run()` is a loop over
        this; the front door calls it from an event loop, interleaving
        `submit`/`cancel` between iterations.

        While a JAX profiler session captures, the iteration records its
        spans (serve/telemetry.py); otherwise it records nothing."""
        if telemetry.active():
            with telemetry.span("serve.step"):
                return self._step(True)
        return self._step(False)

    def _step(self, rec: bool):
        sp = telemetry.span if rec else telemetry.no_span
        while self._heap:
            slot = self._free_slot()
            if slot is None:
                break
            _, _, _, rid, req, t_sub = heapq.heappop(self._heap)
            if rid in self._cancel_pending:
                self._cancel_pending.discard(rid)
                continue
            self._queued_rids.discard(rid)
            now = self._now()
            if rec:
                telemetry.queue_wait(now - t_sub)
            self._admit(req, rid, slot, now, t_sub)

        retired = np.zeros(self.n_slots, bool)
        events: List[Tuple[int, List[int]]] = []
        comps: List[Completion] = []

        # at most ONE prefill chunk per iteration, before the tick
        if self._prefill_q:
            act0 = self._active[self._prefill_q[0]]
            if self._live_host.any():
                self._stall_pending[act0.rid] = \
                    self._stall_pending.get(act0.rid, 0) + 1
            with sp("serve.prefill"):
                sampled, comp, slot = self._prefill_step()
            if sampled:
                self._gen_tokens += sampled
                events.append((act0.rid, [act0.tokens[-1]]))
            if comp is not None:
                comps.append(comp)
                retired[slot] = True

        if not self._live_host.any():
            if retired.any():
                self._scrub(retired)
            return events, comps

        with sp("serve.tick"):
            if self.spec:
                (self.pool, self.draft_pool, self._pending, self._keys,
                 spec_out) = self._spec_tick(
                    self._prm, self._dprm, self.pool, self.draft_pool,
                    self._pending, self._live, self._keys, self._temp,
                    self._topk)
            else:
                self.pool, self._pending, self._keys = self._tick(
                    self._prm, self.pool, self._pending, self._live,
                    self._keys, self._temp, self._topk)
        self.ticks += 1
        if self._stall_pending:
            self._stall_max = max(self._stall_max,
                                  max(self._stall_pending.values()))
            self._stall_pending.clear()
        n_live = int(self._live_host.sum())
        # a prefilling slot is BUSY (it cannot be admitted into), so
        # occupancy counts it — same "slot is taken" meaning as before
        # chunked prefill, when admission held the slot synchronously
        self._occupancy_sum += (n_live + len(self._prefill_q)) / self.n_slots

        # one small device->host transfer per tick: the scheduler needs
        # the sampled ids to detect EOS / quota and to free slots
        now = self._now()
        if self.spec:
            # a spec round emits a VARIABLE number of tokens per slot
            # (accepted prefix + one); truncate at EOS / quota — the
            # overshoot the verify consumed dies with the slot scrub
            out_host = self._read_ids(spec_out, rec)
            for slot in np.flatnonzero(self._live_host):
                act = self._active[slot]
                take = int(out_host[slot, -1])
                self._drafted += self.spec_k
                self._accepted += max(take - 1, 0)
                emitted: List[int] = []
                done = False
                for j in range(take):
                    tok = int(out_host[slot, j])
                    act.tokens.append(tok)
                    emitted.append(tok)
                    self._gen_tokens += 1
                    hit_eos = (self.eos_id is not None
                               and act.tokens[-1] == self.eos_id)
                    if hit_eos or len(act.tokens) >= act.req.max_tokens:
                        done = True
                        break
                events.append((act.rid, emitted))
                if done:
                    comps.append(self._completion(act, int(slot), now))
                    self._retire(int(slot))
                    retired[slot] = True
        else:
            self._gen_tokens += n_live
            toks = self._read_ids(self._pending, rec)
            for slot in np.flatnonzero(self._live_host):
                act = self._active[slot]
                act.tokens.append(int(toks[slot]))
                events.append((act.rid, [int(toks[slot])]))
                hit_eos = (self.eos_id is not None
                           and act.tokens[-1] == self.eos_id)
                if hit_eos or len(act.tokens) >= act.req.max_tokens:
                    comps.append(self._completion(act, int(slot), now))
                    self._retire(int(slot))
                    retired[slot] = True
        if retired.any():
            # scrub the freed slots in ONE batched shape-aware reset:
            # the next occupant prefills IN the slot, so it must read
            # exactly like a fresh one
            self._scrub(retired)
        return events, comps

    # -- internals ----------------------------------------------------------

    @staticmethod
    def _read_ids(ids, rec: bool) -> np.ndarray:
        """The tick's sampled ids on the host.  While recording, the read
        is split into the wait for the tick and the copy of the ready ids;
        otherwise it is the one blocking `np.asarray`."""
        if not rec:
            return np.asarray(ids)
        with telemetry.span("serve.ids_wait"):
            jax.block_until_ready(ids)
        with telemetry.span("serve.ids_copy"):
            return np.asarray(ids)

    def _admit(self, req: Request, rid: int, slot: int, now: float,
               t_submit: float) -> None:
        """Pure bookkeeping plus (with a prefix cache) at most one splice:
        look up the longest cached prompt prefix, copy its carried state
        into the slot row, split the REMAINING prompt into bucket-padded
        chunks and queue the slot for in-slot prefill.  The cached prefix
        is capped at size-1: the last chunk must still run because it
        samples the request's first token."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        cached = 0
        if self.prefix_cache is not None:
            cached, entry = self.prefix_cache.lookup(prompt)
            if entry is not None and self.spec and entry.draft_state is None:
                cached, entry = 0, None  # stored by a non-spec engine: no
            if entry is not None:        # draft half to keep in lockstep
                self._splice_entry(entry, slot)
        chunks: Deque[Tuple[np.ndarray, int]] = deque()
        off = cached
        for Lb, n in self._chunk_plan(prompt.size - cached):
            c = np.zeros(Lb, np.int32)
            c[:n] = prompt[off:off + n]
            off += n
            chunks.append((c, n))
        self._active[slot] = _Active(
            req=req, rid=rid, tokens=[], t_submit=_origin(req, t_submit),
            t_admit=now, t_first=None, chunks=chunks, prompt=prompt,
            off=cached, cached=cached)
        self._prefill_q.append(slot)

    def _splice_entry(self, entry, slot: int) -> None:
        """Copy a cached prefix state into the slot row: widen narrowed
        attention leaves back to pool capacity (zero tail — masked exactly
        like the stale bytes retirement leaves), then ONE full-row write —
        for the RNN family that is the two (L, H) row copies
        `rnn_write_slots` was built from."""
        from repro.serve.prefixcache import widen_state

        sub = widen_state(entry.state, self._ref)
        self.pool = self._splice(self.pool, sub, jnp.int32(slot))
        if self.spec:
            dsub = widen_state(entry.draft_state, self._dref)
            self.draft_pool = self._dsplice(self.draft_pool, dsub,
                                            jnp.int32(slot))

    def _offer_snapshot(self, slot: int, act: _Active) -> None:
        """Offer the slot's carried state to the prefix cache at the
        chunk-boundary offset it just reached (skipped when the boundary is
        already cached — the digest check costs nothing device-side)."""
        from repro.serve.prefixcache import narrow_state

        prefix = act.prompt[:act.off]
        if self.prefix_cache.contains(prefix):
            return
        sub = narrow_state(self._gather(self.pool, jnp.int32(slot)), act.off)
        dsub = None
        if self.spec:
            dsub = narrow_state(
                self._dgather(self.draft_pool, jnp.int32(slot)), act.off)
        self.prefix_cache.insert(prefix, sub, dsub)

    def _prefill_step(self):
        """Run ONE chunk of the oldest prefilling slot.  When the last
        chunk lands, sample the request's first token (stamping the real
        `t_first`) and either turn the slot live or — max_tokens == 1 /
        EOS on the first token — complete it immediately.  Returns
        (n_sampled, completion, retired_slot)."""
        slot = self._prefill_q[0]
        act = self._active[slot]
        chunk, n = act.chunks.popleft()
        # the batch axis is added on the host: an eager `[None]` on the
        # device would build one program per chunk shape that warm() missed
        if self.spec:
            logits, self.pool, self.draft_pool = self._spec_prefill_slot(
                self._prm, self._dprm, self.pool, self.draft_pool,
                jnp.asarray(chunk[None]), jnp.int32(n), jnp.int32(slot))
        else:
            logits, self.pool = self._prefill_slot(
                self._prm, self.pool, jnp.asarray(chunk[None]), jnp.int32(n),
                jnp.int32(slot))
        act.off += n
        if (self.prefix_cache is not None and n == self.prefill_chunk
                and act.off % self.prefill_chunk == 0):
            self._offer_snapshot(slot, act)
        if act.chunks:
            return 0, None, None
        self._prefill_q.popleft()
        req = act.req
        (tok0, self._pending, self._keys, self._temp, self._topk,
         self._live) = self._admit_commit(
            logits, jax.random.PRNGKey(req.seed),
            jnp.float32(req.temperature), jnp.int32(req.top_k),
            self._pending, self._keys, self._temp, self._topk, self._live,
            jnp.int32(slot))
        act.tokens.append(int(tok0))
        act.t_first = self._now()
        if (req.max_tokens <= 1
                or (self.eos_id is not None and act.tokens[0] == self.eos_id)):
            # completed at admission: the device-side live bit was set by
            # the fused commit, and the caller's retired-mask scrub clears
            # it this same scheduler iteration (_live_host stays False, so
            # no host path ever reads the slot as live)
            comp = self._completion(act, slot, act.t_first)
            self._active[slot] = None
            return 1, comp, slot
        self._live_host[slot] = True
        return 1, None, None

    def _completion(self, act: _Active, slot: int, now: float,
                    finished: Optional[str] = None) -> Completion:
        if finished is None:
            hit_eos = (self.eos_id is not None and act.tokens
                       and act.tokens[-1] == self.eos_id)
            finished = "eos" if hit_eos else "length"
        return Completion(
            rid=act.rid, tokens=act.tokens,
            prompt_len=int(act.prompt.size),
            finished=finished, slot=slot,
            t_submit=act.t_submit, t_admit=act.t_admit,
            t_first=act.t_first if act.t_first is not None else act.t_admit,
            t_done=now, cached_tokens=act.cached, slo=act.req.slo)

    def _retire(self, slot: int) -> None:
        # host bookkeeping only: the device-side live bit clears in the
        # iteration's batched _scrub (one jitted call for all retirements)
        self._active[slot] = None
        self._live_host[slot] = False

    def _scrub(self, retired: np.ndarray) -> None:
        """Batched shape-aware reset of the freed slots — state rows, the
        device live mask, and the draft pool's matching rows in speculative
        mode (the next occupant prefills into BOTH pools)."""
        m = jnp.asarray(retired)
        if self.spec:
            self.pool, self.draft_pool, self._live = self._spec_reset(
                self.pool, self.draft_pool, self._live, m)
        else:
            self.pool, self._live = self._reset(self.pool, self._live, m)

    # -- stats (the front door's /v1/stats) ---------------------------------

    def stats(self) -> dict:
        """Cumulative engine-lifetime counters — what a serving fleet
        scrapes.  The trace counters ARE the compile-once invariants."""
        d = {
            "slots": self.n_slots,
            "active": sum(a is not None for a in self._active),
            "queued": len(self._queued_rids),
            "ticks": self.ticks,
            "gen_tokens": self._gen_tokens,
            "tick_traces": self.tick_traces,
            "tick_launches": self.tick_launches,
            "prefill_traces": self.prefill_traces,
            "max_decode_stall_ticks": self._stall_max,
            # programs JAX built since the engine was made (compiles and
            # persistent-cache loads, process-wide): flat once warm
            "compiles": telemetry.compiles() - self._compiles0,
        }
        # per-shard occupancy (queue depth is global — admission is one
        # priority heap feeding every shard): a router in front of a mesh
        # fleet reads this to spot an unbalanced mesh.  A mesh=None engine
        # is one shard, so the schema is unconditional.
        per = self.n_slots // self._data_shards
        busy = [a is not None for a in self._active]
        d["queue_depth"] = d["queued"]
        d["shards"] = [
            {"shard": i, "slots": per,
             "active": int(sum(busy[i * per:(i + 1) * per])),
             "occupancy": sum(busy[i * per:(i + 1) * per]) / per}
            for i in range(self._data_shards)]
        if self.mesh is not None:
            d["mesh"] = {str(a): int(n) for a, n in self.mesh.shape.items()}
        if self.spec:
            d.update({"spec_traces": self.spec_traces,
                      "draft_launches": self.draft_launches,
                      "drafted_tokens": self._drafted,
                      "accepted_drafts": self._accepted})
        if self.prefix_cache is not None:
            d["splice_traces"] = self.splice_traces
            d["prefix_cache"] = self.prefix_cache.stats()
        return d

    def tick_hlo(self) -> str:
        """Compiled HLO of the decode tick over the engine's CURRENT
        operands — the mesh tests grep it with `dispatch.collective_ops`
        to prove the data-sharded tick is communication-free.  Lowering
        re-runs the trace outside the serving path, so the trace/launch
        counters are saved and restored: tick_traces stays a property of
        the SERVING path, not of diagnostics."""
        t, l = self.tick_traces, self.tick_launches
        s, dl = self.spec_traces, self.draft_launches
        try:
            if self.spec:
                low = self._spec_tick.lower(
                    self._prm, self._dprm, self.pool, self.draft_pool,
                    self._pending, self._live, self._keys, self._temp,
                    self._topk)
            else:
                low = self._tick.lower(
                    self._prm, self.pool, self._pending, self._live,
                    self._keys, self._temp, self._topk)
            return low.compile().as_text()
        finally:
            self.tick_traces, self.tick_launches = t, l
            self.spec_traces, self.draft_launches = s, dl

    # -- the batch driver ---------------------------------------------------

    def run(self, requests: Sequence[Request], *, realtime: bool = True):
        """Drive a workload to completion.  Returns (completions, metrics).

        `realtime=True` honours `arrival_s` against the wall clock (traffic
        replay: a request is invisible until it arrives).  `realtime=False`
        treats arrivals as an admission-priority order only — fastest way
        to drain a batch, and what the deterministic parity tests use.

        A thin loop over `submit()` + `step()`: the batch driver and the
        front door run the IDENTICAL scheduler, so everything the fuzz
        harness proves about run() holds for the streaming path too."""
        for r in requests:  # fail fast, BEFORE any request is in flight:
            self._validate(r)  # a bad request must not poison the workload
        arrivals = deque(sorted(requests, key=_arrival))
        completions: List[Completion] = []
        self._t0 = time.perf_counter()
        gen0 = self._gen_tokens
        ticks0, occ0 = self.ticks, self._occupancy_sum  # per-run deltas
        drafted0, accepted0 = self._drafted, self._accepted
        self._stall_pending.clear()
        self._stall_max = 0

        while (arrivals or self._heap or self._prefill_q
               or self._live_host.any()):
            now = self._now()
            # release traffic that has arrived into the admission heap
            while arrivals and (not realtime or _arrival(arrivals[0]) <= now):
                self.submit(arrivals.popleft())
            _, comps = self.step()
            completions.extend(comps)
            if (not self._prefill_q and not self._live_host.any()
                    and not self._heap and arrivals and realtime):
                # idle until the next arrival
                wait = _arrival(arrivals[0]) - self._now()
                if wait > 0:
                    time.sleep(min(wait, 0.05))

        if self._stall_pending:  # prefill work after the last decode tick
            self._stall_max = max(self._stall_max,
                                  max(self._stall_pending.values()))
            self._stall_pending.clear()

        wall = time.perf_counter() - self._t0
        gen_tokens = self._gen_tokens - gen0
        ticks = self.ticks - ticks0
        occ = self._occupancy_sum - occ0
        lat = sorted(c.latency_s for c in completions)
        ttft = sorted(c.ttft_s for c in completions)
        pct = lambda xs, p: (xs[min(len(xs) - 1, int(p * len(xs)))]
                             if xs else 0.0)
        by_cls: Dict[str, List[float]] = {}
        for c in completions:
            by_cls.setdefault(c.slo, []).append(c.ttft_s)
        metrics = {
            "requests": len(completions),
            "wall_s": wall,
            "gen_tokens": gen_tokens,
            "agg_tok_s": gen_tokens / wall if wall > 0 else 0.0,
            "p50_latency_s": pct(lat, 0.50),
            "p95_latency_s": pct(lat, 0.95),
            "ttft_p50_s": pct(ttft, 0.50),
            "ttft_p95_s": pct(ttft, 0.95),
            "ttft_by_class": {
                cls: {"n": len(v), "p50_s": pct(sorted(v), 0.50),
                      "p95_s": pct(sorted(v), 0.95)}
                for cls, v in sorted(by_cls.items())},
            "max_decode_stall_ticks": self._stall_max,
            "ticks": ticks,
            "tick_traces": self.tick_traces,  # cumulative on purpose: the
            "prefill_traces": self.prefill_traces,  # invariants are ==1 and
            "occupancy": occ / ticks if ticks else 0.0,  # <= bucket count
            "tick_launches": self.tick_launches,
        }
        if self.spec:
            drafted = self._drafted - drafted0
            accepted = self._accepted - accepted0
            metrics.update({
                "spec_k": self.spec_k,
                "spec_rounds": ticks,      # every tick is a spec round
                "spec_traces": self.spec_traces,  # cumulative: invariant ==1
                "draft_launches": self.draft_launches,
                "drafted_tokens": drafted,
                "accepted_drafts": accepted,
                "accept_rate": accepted / drafted if drafted else 0.0,
                # drafted/s measures the packed proposer's raw speed; the
                # headline agg_tok_s is emitted (target-quality) tokens/s
                "draft_tok_s": drafted / wall if wall > 0 else 0.0,
            })
        if self.prefix_cache is not None:
            metrics["splice_traces"] = self.splice_traces
            metrics["prefix_cache"] = self.prefix_cache.stats()
        return completions, metrics
