"""Backend-honest kernel dispatch (DESIGN.md §11).

One policy decides, per backend, how a packed-weight op actually runs — so
recorded numbers always measure real work and CPU never silently executes
interpret-mode Pallas emulation in a serving path:

    backend   packed matmul / decode tick        interpret-mode Pallas
    -------   -------------------------------    ----------------------
    tpu       compiled Pallas kernel             never
    cpu, gpu  dense fp fallback (weights are     opt-in ONLY (parity
              dequantized ONCE per session;      tests pass
              memory stays the packed codes)     interpret=True)

The kernels use TPU-only Pallas features (VMEM scratch, Mosaic compiler
parameters), so no other backend compiles them.

Every entry that used to make this call locally (`rnn_decode_tables(dense=)`,
`qmatmul`, the decode-step wrappers) now asks this module.  The convention
shared by all of them: an `interpret`/`dense` argument of None means "do the
honest thing for this backend"; an explicit value is a caller opt-in (the
parity suites run the interpret kernels against the dense fallback on CPU).

The module also owns two proof utilities the tier-1 tests assert on:

  * a TRACE-TIME launch counter — every `pl.pallas_call` wrapper in this
    package bumps it once per launch it traces, so "the decode tick is ONE
    fused launch" is counted the same way the engine counts `tick_traces`,
    not inferred from profiles;
  * `assert_accumulation_only` — walks a function's jaxpr (recursively
    through scan/cond/pjit sub-jaxprs) and fails if any `mul`/`dot_general`
    survives, the static form of the paper's multiply-free weight path.
"""
from __future__ import annotations

from typing import Optional

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr


def backend() -> str:
    """The platform actually executing jitted code ('cpu', 'tpu', 'gpu')."""
    return jax.default_backend()


def prefer_dense(dense: Optional[bool] = None) -> bool:
    """Should a serving session expand packed weights into dense fp tables?

    None -> the backend policy: False on the TPU (the fused packed kernels
    are the whole point), True elsewhere (packed Pallas would only run
    emulated there).  An explicit bool is a caller override.
    """
    if dense is not None:
        return dense
    return backend() != "tpu"


def use_pallas(interpret: Optional[bool] = None) -> bool:
    """Should this op run a Pallas kernel at all?

    False off the TPU with no explicit `interpret` request — that is the
    dense-fallback case.  `interpret=True` is the parity-test opt-in
    (emulated kernel, real kernel semantics); on the TPU the compiled
    kernel always runs.
    """
    if interpret is not None:
        return True
    return backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret flag for a Pallas call that IS going to run: None means
    'compile on the TPU, emulate elsewhere' (direct kernel entries keep
    working on CPU for tests that did not pass an explicit flag)."""
    if interpret is not None:
        return interpret
    return backend() != "tpu"


# ---------------------------------------------------------------------------
# trace-time launch counter
# ---------------------------------------------------------------------------

_launches: dict[str, int] = {}


def count_launch(name: str) -> None:
    """Called by every pallas_call wrapper in kernels/ at TRACE time, once
    per launch it emits into the computation being traced, under the
    kernel's name (`launch_counts`).  Like the engine's `tick_traces`,
    the count is a property of the traced program, not of executions — a
    jitted tick that traces N launches dispatches N kernels every call
    thereafter."""
    _launches[name] = _launches.get(name, 0) + 1


def launch_count() -> int:
    """Monotonic total of Pallas launches traced so far; callers diff it
    around a trace to count launches-per-tick."""
    return sum(_launches.values())


def launch_counts() -> dict[str, int]:
    """Monotonic tally of traced launches per name (a copy); callers diff
    it around a trace to see which kernels a program holds."""
    return dict(_launches)


def traced_launches(fn, *args, **kwargs) -> int:
    """Launches the jitted form of `fn(*args)` dispatches per call: trace it
    once (abstractly — nothing executes) and diff the counter."""
    before = launch_count()
    jax.eval_shape(lambda *a: fn(*a, **kwargs), *args)
    return launch_count() - before


# ---------------------------------------------------------------------------
# static mul-freeness proof
# ---------------------------------------------------------------------------

_MULTIPLY_PRIMS = ("mul", "dot_general", "conv_general_dilated")


def _sub_jaxprs(v):
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def _multiply_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _MULTIPLY_PRIMS:
            yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _multiply_eqns(sub)


def assert_accumulation_only(fn, *args, **kwargs):
    """Statically prove `fn(*args, **kwargs)` contains NO multiplies.

    Walks the jaxpr (recursing into scan/cond/pjit bodies) and raises
    AssertionError listing every `mul`/`dot_general`/conv equation found.
    The packed GEMV path is asserted with this in tier-1: the decoded
    weights are consumed by select/add/subtract ONLY — the paper's
    replace-every-MAC-with-an-accumulation claim, as a compiler fact."""
    import functools

    closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    bad = list(_multiply_eqns(closed.jaxpr))
    if bad:
        lines = "\n  ".join(str(e) for e in bad[:8])
        raise AssertionError(
            f"{len(bad)} multiply op(s) in supposedly accumulation-only "
            f"path:\n  {lines}")
    return closed


# ---------------------------------------------------------------------------
# mesh-serving proofs and gates
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter")


def collective_ops(hlo_text: str) -> list:
    """Collective-communication ops named in compiled HLO text.

    The third proof utility (after the launch counter and mul-freeness):
    the mesh serving tests compile the data-sharded decode tick and assert
    this returns [] — every op of the tick is shard-local, so adding slot
    shards never adds wire traffic (DESIGN.md §12).  Tensor-parallel ticks
    legitimately contain reductions and are NOT asserted collective-free.
    """
    low = hlo_text.lower()
    return [c for c in _COLLECTIVES if c in low]


def packed_pallas_active(tree) -> bool:
    """True when serving `tree` on this backend would dispatch the packed
    Pallas kernels (QTensor leaves present and the backend runs Pallas).

    A mesh-sharded engine must refuse that combination today: pallas_call
    is a single-device launch, so running it over a sharded slot pool or
    sharded codes needs a shard_map port (ROADMAP).  On CPU the same tree
    serves through the compiled dense fallback, whose dequantize + dot
    partition cleanly under SPMD — which is what makes the whole mesh
    story CI-provable on host devices."""
    from repro.core.qtensor import is_qtensor
    if not use_pallas(None):
        return False
    return any(is_qtensor(l) for l in
               jax.tree_util.tree_leaves(tree, is_leaf=is_qtensor))
