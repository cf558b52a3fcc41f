"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — 'pod' is an
outer data-parallel axis whose gradient all-reduce crosses DCN.

Defined as functions (not module constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """`jax.make_mesh` with Auto axes.  Since jax 0.9 the default axis type
    is Explicit, which types every sharded array by its layout and refuses
    ops (a dynamic slot gather on a 'data'-sharded pool) whose output
    sharding it cannot infer; the engine and trainer pin in/out shardings
    on their jits and let the partitioner place the rest, which is Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — run "
            f"under dryrun.py (it forces 512 host devices) or on the pod")
    return _mesh(shape, axes, devices[:n])


def make_host_mesh(model: int = 1):
    """Mesh over whatever is locally available (tests, CPU).

    `model` must divide the local device count.  This used to gcd-shrink the
    model axis silently, which meant `make_host_mesh(model=4)` on 6 devices
    handed back a model=2 mesh and tensor-parallel tests quietly ran at half
    the requested width — now it raises and names the shape the fallback
    would have produced.
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model={model} must be >= 1")
    if n % model:
        g = math.gcd(model, n)
        raise ValueError(
            f"model={model} does not divide the {n} local devices; the old "
            f"silent fallback would have built a data={n // g},model={g} "
            f"mesh — pass model={g} explicitly if that is what you want")
    data = n // model
    if model > 1:
        return _mesh((data, model), ("data", "model"))
    return _mesh((n,), ("data",))


def parse_mesh_spec(spec: str) -> dict:
    """``"data=4,model=2"`` → ``{"data": 4, "model": 2}`` (absent axes = 1)."""
    sizes = {"data": 1, "model": 1}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad mesh spec part {part!r} (want axis=N)")
        axis, _, num = part.partition("=")
        axis = axis.strip()
        if axis not in sizes:
            raise ValueError(
                f"unknown mesh axis {axis!r} (serving meshes have data, model)")
        sizes[axis] = int(num)
        if sizes[axis] < 1:
            raise ValueError(f"mesh axis {axis}={sizes[axis]} must be >= 1")
    return sizes


def make_serve_mesh(spec: str):
    """Serving mesh for ``--mesh data=D,model=M`` over the first D*M local
    devices.  Both axes always exist (size-1 axes are fine — the sharding
    rules' divisibility gates treat them as replication), so one code path
    in the engine covers DP-only, TP-only, and DP×TP."""
    sizes = parse_mesh_spec(spec)
    d, m = sizes["data"], sizes["model"]
    devices = jax.devices()
    if d * m > len(devices):
        raise ValueError(
            f"mesh data={d},model={m} needs {d * m} devices, have "
            f"{len(devices)} — on CPU run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return _mesh((d, m), ("data", "model"), devices[: d * m])
