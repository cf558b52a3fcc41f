"""Where the harness finds each piece, by the name `BENCHMARK.json` gives:

  configuration <c>   bench/configs/<c>.json; its "model" names
                      bench/models/<model>.py (the system under test) and,
                      through it, the plain reference beside it
  traffic mix <m>     bench/traffic/<m>.json, read by bench/traffic.py
  cell <w>            its entry in BENCHMARK.json, and bench/limits/<w>.json
                      (the limits of the numbers that decide `correct`,
                      with the readings they were set from)
  per-layer metric <p>  bench/metrics/<p>.py, a reader `read(ctx)`

so a later change adds a configuration, a mix, a cell or a metric by adding
files and entries, and edits none."""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return _json(BENCH / "limits" / f"{workload_name}.json")


def model(name: str):
    return importlib.import_module(f"bench.models.{name}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reports(metric: dict, workload_name: str, e2e_names) -> bool:
    """Does this cell report `metric`?  A metric with a `workloads` list is
    reported in those cells; one without it in every cell (an end-to-end
    metric) or in every cell that reports the metric it moves (per-layer)."""
    if "workloads" in metric:
        return workload_name in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names
