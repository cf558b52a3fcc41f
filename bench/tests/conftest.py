"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

`small_cells` shrinks every configuration and traffic mix to a size the
CPU runs in seconds (the same code paths; the engine serves through the
program's dense fallback off the TPU)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


def shrink_config(c: dict) -> dict:
    c = dict(c, d_hidden=64)
    c["deployment"] = dict(c["deployment"], slots_per_chip=4)
    return c


def shrink_traffic(t: dict) -> dict:
    t = dict(t)
    t["prompt"] = dict(t["prompt"], max=min(t["prompt"]["max"], 96))
    if "median" in t["prompt"]:
        t["prompt"]["median"] = 40
    t["output"] = dict(t["output"], max=min(t["output"]["max"], 160),
                       min=min(t["output"]["min"], 24))
    if "median" in t["output"]:
        t["output"]["median"] = 64
    if t["loop"] == "open":
        t.update(rate_per_s=4.0, lead_in_s=2.0)
    return t


@pytest.fixture
def small_cells(monkeypatch):
    from bench import spec as SPEC

    config, traffic = SPEC.config, SPEC.traffic
    monkeypatch.setattr(SPEC, "config", lambda n: shrink_config(config(n)))
    monkeypatch.setattr(SPEC, "traffic", lambda n: shrink_traffic(traffic(n)))
    return SPEC
