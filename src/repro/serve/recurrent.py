"""Unified recurrent serving runtime (DESIGN.md §6).

One stateful prefill/decode interface over every decoder in the repo — the
paper's BN-LSTM/BN-GRU, RWKV6, Mamba2, and the attention families:

    rt = serving_runtime(cfg, params)          # RNNConfig or ModelConfig
    state = rt.init_state(batch, context)
    logits, state = rt.prefill(tokens, state)  # (B, V) last-token logits
    logits, state = rt.decode_step(tok, state) # tok: (B,) int32

`state` is an opaque pytree the caller threads, never inspects:

  * BN-LSTM/GRU — `bnlstm.RNNState` (stacked per-layer h/c).  The runtime
    builds the per-session decode tables ONCE (frozen-BN affines, the
    dequantized+BN-folded layer-0 row table, the stacked whole-tick kernel
    artifact) and passes them into the jitted step, so a packed tree
    decodes through ONE fused Pallas launch per tick with no per-call
    re-preparation — or, on CPU, through dense fp tables (backend-honest
    dispatch, kernels/dispatch.py).
  * transformer pool — the `T.init_caches` pytree.  For RWKV6 / Mamba2
    layers the cache slots hold `RWKVState` / `SSMState` and the decode step
    runs `wkv6_step` / `ssd_step`; attention layers hold KV caches in the
    same slots.  The runtime treats both identically.

The launcher (`launch/serve.py`), the serve engine and the serving tests
all drive this interface, so every arch exercises the same prefill →
sample → decode loop.
"""
from __future__ import annotations

import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bnlstm as BL
from repro.core.qtensor import tree_nbytes
from repro.configs.shapes import decode_context
from repro.models import transformer as T
from repro.serve.sampler import sample

Array = jax.Array


def state_nbytes(state: Any) -> int:
    """Bytes a session's recurrent state occupies (KV caches / S-matrices /
    h,c vectors alike) — the per-session memory a serving fleet provisions."""
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(state)
               if hasattr(l, "dtype"))


class RNNRuntime:
    """BN-LSTM / BN-GRU serving session (core/bnlstm.py serving entry)."""

    family = "rnn"
    # chunked in-slot prefill (DESIGN.md §8): the O(1) recurrent carry makes
    # any split exact, and the masked scan makes bucket padding exact — the
    # engine compiles one prefill trace per power-of-two bucket, ever.
    chunk_granularity = "token"
    pad_buckets = True
    # speculative decoding (DESIGN.md §9): verify is a scan of the exact
    # decode-step body, rollback a per-step-state SELECT — always exact.
    spec_capable = True

    def __init__(self, cfg: BL.RNNConfig, variables: dict, *,
                 interpret: Optional[bool] = None,
                 dense_tables: Optional[bool] = None):
        from repro.kernels import dispatch

        self.cfg = cfg
        self.variables = variables
        self._interpret = interpret
        # dense_tables=None lets kernels/dispatch.py pick the backend-honest
        # path: dense fp tables off the TPU (no interpret-mode Pallas in serving),
        # packed tables + the whole-tick fused kernel on the TPU.  Parity
        # tests opt into packed-on-CPU with dense_tables=False +
        # interpret=True.
        self._dense_tables = dispatch.prefer_dense(dense_tables)
        # once per session: dequantized layer-0 rows, BN affines, and (when
        # packed) the stacked whole-tick kernel artifact — see
        # rnn_decode_tables
        self.tables = BL.rnn_decode_tables(variables, cfg,
                                           dense=self._dense_tables)
        def prefill_last(v, tb, toks, st):
            # take the last-token logits from the carried state through the
            # shared (B, 1, H) head (rnn_logits_last): XLA never
            # materializes the (B, T, vocab) prompt logits the serving loop
            # discards, and the chunked engine's first-token sample — which
            # uses the same helper — is bit-identical to this one
            _, st = BL.rnn_prefill(v, toks, cfg, st, tables=tb)
            return BL.rnn_logits_last(v, st, cfg), st

        self._prefill = jax.jit(prefill_last)
        self._decode = jax.jit(
            lambda v, tb, tok, st: BL.rnn_decode_step(
                v, tok, cfg, st, tables=tb, interpret=interpret))

    def init_state(self, batch: int, context: int = 0, *,
                   per_slot: bool = False) -> BL.RNNState:
        del context  # constant-size state: the RNN's whole point
        return BL.rnn_state_init(self.cfg, batch, per_slot=per_slot)

    def prefill(self, tokens: Array, state: BL.RNNState):
        return self._prefill(self.variables, self.tables, tokens, state)

    def decode_step(self, tok: Array, state: BL.RNNState):
        return self._decode(self.variables, self.tables, tok, state)

    @property
    def jit_prm(self):
        """The pytree a caller jitting its own region must thread as an
        ARGUMENT into `decode_fn`/`prefill_chunk`/`verify` (the engine's
        tick/prefill jits do): closing over weights instead lets XLA
        constant-fold them, which shifts logits ~1ulp vs the arg-passed
        `drive_session` jits and makes logits-level comparisons unsound."""
        return (self.variables, self.tables)

    def serve_prm_shardings(self, mesh):
        """Mesh placement of `jit_prm` for a sharded ServeEngine: fully
        REPLICATED.  The fused (H, 4H) gate weight cannot column-shard over
        'model' without splitting the i/f/g/o gates across shards (the
        `split(4)` boundary lands mid-axis), which would turn the f*c + i*g
        elementwise math into cross-shard traffic — and at paper scale the
        packed LSTM is a few hundred KB, so replication is the right call.
        Data-sharding of the slot pool is untouched by this: rows of the
        tick read replicated weights shard-locally."""
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(lambda _: rep, self.jit_prm)

    def decode_fn(self, tok: Array, state: BL.RNNState,
                  live: Optional[Array] = None, prm=None):
        """Unjitted decode body for callers that jit a larger region (the
        continuous-batching engine's tick).  `live` (B,) masks dead slots:
        their h/c/pos stay bit-for-bit frozen inside the fused kernel.
        `prm` is the caller's traced `jit_prm` (None: close over self's —
        only sound outside jit)."""
        var, tb = prm if prm is not None else (self.variables, self.tables)
        return BL.rnn_decode_step(var, tok, self.cfg, state,
                                  tables=tb, live=live,
                                  interpret=self._interpret)

    def prefill_chunk(self, tokens: Array, state: BL.RNNState, n: Array,
                      prm=None):
        """Unjitted bucket-padded chunk body (engine jits gather+chunk+write
        as one region): consume the first `n` of tokens, carry the state."""
        var, tb = prm if prm is not None else (self.variables, self.tables)
        return BL.rnn_prefill_chunk(var, tokens, self.cfg, state,
                                    n=n, tables=tb)

    def write_slots(self, state: BL.RNNState, sub: BL.RNNState, slots):
        return BL.rnn_write_slots(state, sub, slots)

    # -- speculative decoding (DESIGN.md §9) --------------------------------
    # The RNN's rollback story is the O(1) state again: every step of a
    # draft/verify span EMITS its (h, c) carry, and committing n tokens is a
    # per-slot select over the emitted stack — no byte surgery, n = 0 IS the
    # pre-span state.  spec_snapshot therefore has nothing to save.

    def spec_snapshot(self, state: BL.RNNState, span: int):
        del state, span
        return ()

    def spec_emit(self, state: BL.RNNState):
        """Per-step rollback material emitted inside the engine's draft
        scan: the carried h/c stacks (pos is recomputed at commit)."""
        return (state.h, state.c)

    def verify(self, tokens: Array, state: BL.RNNState,
               live: Optional[Array] = None, prm=None):
        """Multi-token target step (unjitted body — the engine jits the
        whole spec tick): (B, T) tokens -> (logits (B, T, V), end state,
        per-step emits).  Bit-identical per position to T decode steps."""
        var, tb = prm if prm is not None else (self.variables, self.tables)
        return BL.rnn_verify(var, tokens, self.cfg, state,
                             tables=tb, live=live,
                             interpret=self._interpret)

    def spec_commit(self, state0: BL.RNNState, state_after: BL.RNNState,
                    snap, emits, n: Array) -> BL.RNNState:
        del state_after, snap
        return BL.rnn_spec_commit(state0, emits, n)

    def param_nbytes(self) -> tuple[int, int]:
        return tree_nbytes(self.variables["params"])


class TransformerRuntime:
    """Transformer-pool serving session — includes the recurrent members
    (rwkv6-7b, the zamba2 hybrids), whose decode steps are
    `wkv6_step`/`ssd_step` carried inside the cache pytree."""

    family = "transformer"

    def __init__(self, cfg, params, *, extras: Optional[dict] = None):
        self.cfg = cfg
        self.params = params
        self.extras = dict(extras or {})
        self._prefill = jax.jit(
            lambda p, t, c: T.prefill(p, t, c, cfg, **self.extras))
        self._decode = jax.jit(lambda p, t, c: T.decode_step(p, t, c, cfg))
        # chunked in-slot prefill policy (DESIGN.md §8).  Splitting a prompt
        # mid-sequence is exact when every layer carries its state across
        # the split: attention through its cache, mamba2 through h and the
        # conv tail (a bucket-pad token gets dt = 0 and x = 0, so padding
        # is exact too).  rwkv re-chunks its internal scan at different
        # boundaries and MoE capacity competition spans the whole slice, so
        # those archs prefill the prompt as ONE in-slot chunk.  Bucket
        # PADDING additionally requires that pad writes land past the
        # rewound pos in a non-ring cache — sliding-window rings recycle
        # those slots, so they chunk exactly.
        pat, rep, tail = T.expand_pattern(cfg)
        kinds = set(pat) | set(tail)
        whole = "rwkv" in kinds or cfg.n_experts > 0
        self.chunk_granularity = "whole" if whole else "token"
        self.pad_buckets = (not whole) and not cfg.swa_all and \
            "local" not in kinds
        # speculative decoding needs (a) a multi-token step that is
        # per-token exact with (b) non-ring caches, so a rejected suffix
        # can be rolled back without having recycled in-window history —
        # the pad_buckets predicate — and (c) no recurrent mixer, whose
        # state a rejected suffix has already advanced (ROADMAP R4)
        self.spec_capable = self.chunk_granularity == "token" and \
            self.pad_buckets and "mamba" not in kinds

    def init_state(self, batch: int, context: int, *,
                   per_slot: bool = False):
        _, src = decode_context(self.cfg, context)
        return T.init_caches(self.cfg, batch, context, src_len=src,
                             dtype=jnp.dtype(self.cfg.dtype),
                             per_slot=per_slot)

    def prefill(self, tokens: Array, state):
        return self._prefill(self.params, tokens, state)

    def decode_step(self, tok: Array, state):
        return self._decode(self.params, tok, state)

    @property
    def jit_prm(self):
        """The param tree a caller's own jit must thread as an argument (see
        RNNRuntime.jit_prm — same constant-folding rationale)."""
        return self.params

    def serve_prm_shardings(self, mesh):
        """Mesh placement of `jit_prm` for a sharded ServeEngine: the
        name-based serving rules (tensor-parallel over 'model', no FSDP
        axis), with packed QTensor leaves projected onto their codes —
        column-parallel Wq/Wk/Wv/Wup shard the codes' output-column axis
        directly, row-parallel Wo/Wdown shard the packed rows when the pack
        group divides cleanly.  This is how the large configs serve at
        size: each model shard holds 1/M of every weight's codes."""
        from repro.launch.sharding import serve_param_shardings
        return serve_param_shardings(self.params, mesh)

    def decode_fn(self, tok: Array, state, live: Optional[Array] = None,
                  prm=None):
        """Unjitted decode body for callers that jit a larger region (the
        continuous-batching engine's tick).  `live` (B,) freezes dead rows'
        cache writes and recurrent states bit-for-bit — with in-slot
        chunked prefill a dead row can be a slot MID-PREFILL, so the old
        zombie-writes-are-harmless argument no longer holds.  Dead rows'
        logits stay garbage; the engine never samples them."""
        p = prm if prm is not None else self.params
        return T.decode_step(p, tok, state, self.cfg, live=live)

    def prefill_chunk(self, tokens: Array, state, n: Array, prm=None):
        """Unjitted prompt-chunk body (engine jits gather+chunk+write as one
        region): consume the first `n` of tokens against the carried cache;
        bucket padding past `n` is rewound off the attention pos."""
        p = prm if prm is not None else self.params
        return T.prefill(p, tokens, state, self.cfg, n=n)

    # -- speculative decoding (DESIGN.md §9) --------------------------------
    # Rollback here is byte surgery on the caches: snapshot the span of
    # k/v bytes a draft/verify is about to overwrite, and commit restores
    # the rejected suffix and rewinds each slot's pos — the committed cache
    # is bit-identical to one that only ever saw the accepted prefix.

    def _is_cache(self, x) -> bool:
        from repro.serve.kvcache import AttnCache
        return isinstance(x, AttnCache)

    def spec_snapshot(self, state, span: int):
        from repro.serve.kvcache import cache_spec_snapshot
        return jax.tree.map(lambda c: cache_spec_snapshot(c, span),
                            state, is_leaf=self._is_cache)

    def spec_emit(self, state):
        del state  # the snapshot carries all rollback material
        return ()

    def verify(self, tokens: Array, state, live: Optional[Array] = None,
               prm=None):
        """Multi-token target step (unjitted body — the engine jits the
        whole spec tick): (B, T) tokens -> (logits (B, T, V), caches, ()).
        Per-position logits through the decode head shape; bit-identical
        per position to T decode steps (tests/test_spec_decode.py)."""
        p = prm if prm is not None else self.params
        logits, state = T.verify_step(p, tokens, state, self.cfg,
                                      live=live)
        return logits, state, ()

    def spec_commit(self, state0, state_after, snap, emits, n: Array):
        del state0, emits
        from repro.serve.kvcache import cache_spec_commit
        return jax.tree.map(lambda c, s: cache_spec_commit(c, s, n),
                            state_after, snap, is_leaf=self._is_cache)

    def param_nbytes(self) -> tuple[int, int]:
        return tree_nbytes(self.params)


def serving_runtime(cfg, params, **kw):
    """The one constructor: RNNConfig -> RNNRuntime (params is the
    {'params', 'state'} variables dict), ModelConfig -> TransformerRuntime."""
    if isinstance(cfg, BL.RNNConfig):
        return RNNRuntime(cfg, params, **kw)
    return TransformerRuntime(cfg, params, **kw)


def speculative_draft(rt, mode: str = "ternary",
                      dense: Optional[bool] = None):
    """Self-speculation pairing (DESIGN.md §9): pack the target runtime's
    OWN master weights into a binary/ternary draft runtime.

    The paper's whole hardware argument — packed weights decode ~10x faster
    in ~12x less memory — is the profile of an ideal draft model, and
    because the draft is a QTensor export of the very tree the fp target
    serves, the two track closely and acceptance stays high.  The returned
    runtime shares the target's config dims (and, for the RNN, its frozen
    BN statistics), so the engine can drive both pools through identical
    prefill plans.

    `dense` (RNN drafts): expand the packed weights into dense decode
    tables once per session.  None defers to the backend dispatch policy
    (kernels/dispatch.py): dense on CPU, where the draft's job is raw step
    latency and packed Pallas would only run emulated; on real accelerators
    the draft keeps the whole-tick fused packed kernel."""
    import dataclasses

    from repro.core.qtensor import export_packed, is_qtensor
    from repro.core.quantize import QuantSpec

    if isinstance(rt, RNNRuntime):
        wx0 = rt.variables["params"]["layers"][0]["wx"]
        if is_qtensor(wx0):
            raise ValueError(
                "speculative pairing packs the target's fp masters; this "
                "runtime already serves a packed tree — build the pair "
                "from the master weights instead")
        dcfg = dataclasses.replace(
            rt.cfg, quant=QuantSpec(mode=mode, norm="batch"))
        packed = BL.export_packed_rnn(rt.variables["params"], dcfg)
        return RNNRuntime(dcfg, {"params": packed,
                                 "state": rt.variables["state"]},
                          interpret=rt._interpret, dense_tables=dense)
    if any(is_qtensor(l) for l in jax.tree_util.tree_leaves(
            rt.params, is_leaf=is_qtensor)):
        raise ValueError(
            "speculative pairing packs the target's fp masters; this "
            "runtime already serves a packed tree — build the pair from "
            "the master weights instead")
    dcfg = rt.cfg.with_quant(QuantSpec(mode=mode, norm="channel"))
    return TransformerRuntime(dcfg, export_packed(rt.params, dcfg.quant),
                              extras=dict(rt.extras))


def drive_session(rt, prompt: Array, vocab: int, *, gen: int,
                  temperature: float = 0.8, top_k: int = 0, seed: int = 0,
                  context: Optional[int] = None):
    """The canonical prefill -> sample -> decode session, timed (the
    launcher's lockstep mode, and the sequential oracle the engine tests
    compare against).

    `context` overrides the provisioned context length (default: exactly
    S + gen).  The engine parity tests pass the engine pool's max_context so
    the sequential baseline attends over an identically-sized cache.

    Returns (generated (B, gen) int array, metrics dict with prefill/decode
    seconds, tok/s, and the per-session state bytes)."""
    B, S = prompt.shape
    context = context or (S + gen)
    state = rt.init_state(B, context)
    # clean-state invariant: every position counter of a fresh state is 0
    # (the float leaves are zeros by construction)
    assert all(int(jnp.sum(l)) == 0
               for l in jax.tree_util.tree_leaves(state)
               if hasattr(l, "dtype") and jnp.issubdtype(l.dtype, jnp.integer))

    t0 = time.perf_counter()
    logits, state = rt.prefill(prompt, state)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    toks = []
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    for _ in range(gen):
        key, sk = jax.random.split(key)
        nxt = sample(logits, sk, temperature=temperature, top_k=top_k,
                     vocab=vocab)
        # accumulate ON DEVICE: np.asarray here would block on the transfer
        # every iteration and the recorded decode tok/s would measure host
        # round-trips, not the serving path
        toks.append(nxt)
        logits, state = rt.decode_step(nxt, state)
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    out = np.stack([np.asarray(t) for t in toks], axis=1)
    metrics = {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "prefill_tok_s": B * S / t_prefill,
        "decode_tok_s": B * gen / t_decode,
        "state_nbytes": state_nbytes(state),
    }
    return out, metrics
