"""Process-wide runtime context: which logical mesh axes exist right now.

Model code calls `constrain(x, *axes)` to request activation shardings; outside
a mesh context (unit tests, single-device runs) this is a no-op, inside the
dry-run / trainer it becomes `with_sharding_constraint`.  Axes that do not
divide the corresponding dimension are dropped (e.g. 8 KV heads on a 16-way
'model' axis -> replicated), so one set of rules serves every (arch x mesh).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def current_mesh() -> Optional[jax.sharding.Mesh]:
    return getattr(_state, "mesh", None)


def current_param_rules():
    return getattr(_state, "param_rules", None)


def current_compute_rules():
    return getattr(_state, "compute_rules", None)


def abstract_mesh(sizes: Sequence[int], names: Sequence[str]):
    """Device-free mesh for spec-building and tests."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(sizes), tuple(names))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, jax already keeps the cache
    there and nothing else is set here.  Otherwise the cache lives at one
    fixed path in the checkout, `<repo>/.jax_cache` (git-ignored), so the
    next process run from the same checkout finds what this one compiled; a
    per-process or temporary directory would start empty every run.
    Launchers call this before their first compile; tests never do."""
    import os
    from pathlib import Path

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_banner() -> str:
    """What this process runs on, as JAX reports it: platform, device kind
    and device count — printed by every launcher, so no output can be read
    as a chip result when it came from the CPU."""
    devs = jax.devices()
    return (f"device: platform={devs[0].platform} "
            f"kind={devs[0].device_kind} count={len(devs)}")


@contextlib.contextmanager
def use_mesh(mesh: Optional[jax.sharding.Mesh], param_rules=None,
             compute_rules=None):
    """`param_rules(path, leaf, mesh) -> PartitionSpec` (optional) lets inner
    code (e.g. the quantizer) pin intermediates to the parameter STORAGE
    layout; `compute_rules` gives the layout of the transient COMPUTE copy
    (bf16 / unpacked weights) that the matmuls consume — see constrain_param."""
    prev = current_mesh()
    prev_rules = current_param_rules()
    prev_crules = current_compute_rules()
    _state.mesh = mesh
    _state.param_rules = param_rules
    _state.compute_rules = compute_rules
    # AbstractMesh (tests / spec-building) is not a context manager
    is_concrete = isinstance(mesh, jax.sharding.Mesh)
    ctx = mesh if is_concrete else contextlib.nullcontext()
    try:
        with ctx:
            yield mesh
    finally:
        _state.mesh = prev
        _state.param_rules = prev_rules
        _state.compute_rules = prev_crules


def constrain_param(path, master: jax.Array, derived: jax.Array,
                    drop_axes: Sequence[str] = (),
                    kind: str = "storage") -> jax.Array:
    """Constrain `derived` (e.g. a quantized weight) to the sharding the
    parameter rules give `master`.  This forces elementwise work (stochastic
    quantization, bf16 cast, bit packing) to run shard-local, so the FSDP
    all-gather moves the small derived tensor instead of fp32 masters.

    `drop_axes` removes mesh axes from the spec (replicating those dims) —
    used to place the UNPACKED weight after a packed gather: packed is
    (data, model)-sharded, unpacked is model-only, so the SPMD reshard
    (the all-gather over 'data') happens on the 2-bit codes."""
    mesh, rules = current_mesh(), current_param_rules()
    if kind == "compute" and current_compute_rules() is not None:
        rules = current_compute_rules()
        drop_axes = ()
    if mesh is None or rules is None:
        return derived
    spec = rules(path, master, mesh)
    if drop_axes:
        def keep(a):
            if a is None:
                return None
            if isinstance(a, tuple):
                kept = tuple(x for x in a if x not in drop_axes)
                return kept if len(kept) > 1 else (kept[0] if kept else None)
            return None if a in drop_axes else a
        spec = jax.sharding.PartitionSpec(*[keep(a) for a in spec])
    # rank matches (packed keeps rank; K/GROUP axis reuses K's spec) but the
    # packed dim may no longer divide — drop axes that don't fit.
    entries = list(tuple(spec)[: derived.ndim])
    entries += [None] * (derived.ndim - len(entries))
    fixed = []
    for dim, a in zip(derived.shape, entries):
        if a is None:
            fixed.append(None)
            continue
        axes = a if isinstance(a, tuple) else (a,)
        n = 1
        for x in axes:
            n *= mesh.shape.get(x, 1)
        fixed.append(a if dim % n == 0 else None)
    spec = jax.sharding.PartitionSpec(*fixed)
    return jax.lax.with_sharding_constraint(
        derived, jax.sharding.NamedSharding(mesh, spec))


def _fit(dim: int, axes, mesh) -> Optional[object]:
    """Return the largest prefix of `axes` whose product divides `dim`."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    keep = []
    prod = 1
    for a in axes:
        if a not in mesh.shape:
            continue
        n = mesh.shape[a]
        if dim % (prod * n) == 0:
            keep.append(a)
            prod *= n
        else:
            break
    if not keep:
        return None
    return tuple(keep) if len(keep) > 1 else keep[0]


def spec_for(shape: Sequence[int], *axes) -> P:
    """Build a PartitionSpec, silently replicating non-divisible dims."""
    mesh = current_mesh()
    if mesh is None:
        return P()
    return P(*[_fit(d, a, mesh) for d, a in zip(shape, axes)])


def constrain(x: jax.Array, *axes) -> jax.Array:
    """Sharding-constrain x per logical axes; no-op outside a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec_for(x.shape, *axes))
