"""BENCHMARK.json against the rules it is held to, and every name in it
resolved to its files the way the harness resolves them."""
import json
import re

import pytest

from bench import spec as SPEC

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
B = SPEC.benchmark()


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"] and B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51 and isinstance(B["run_seconds"], int)
    assert len(json.dumps(B)) < 64 * 1024


def test_configs_resolve():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        f = SPEC.config(c["name"])
        assert f["reduced"] == c["reduced"] == []
        SPEC.model(f["model"])
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}


def test_workloads_resolve():
    names = [w["name"] for w in B["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = 0
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
        four += w["chips"] == 4
        SPEC.traffic(w["traffic"])
        lim = SPEC.limits(w["name"])
        assert set(lim) >= {"greedy_gap", "state_err"}
        assert SPEC.config(w["config"])["deployment"]["chips"] == w["chips"]
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("m", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(m):
    e2e = {x["name"] for x in B["end_to_end"]}
    keys = {"name", "unit", "better", "source"}
    if m["name"] in e2e:
        keys |= {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        keys |= {"layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        SPEC.metric_reader(m["name"])       # its reader is there
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in B["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


def test_every_cell_reports_enough():
    e2e_all = B["end_to_end"]
    for w in B["workloads"]:
        e2e = [m["name"] for m in e2e_all if SPEC.reports(m, w["name"], ())]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = [m for m in B["per_layer"] if SPEC.reports(m, w["name"], e2e)]
        assert per
        for m in per:
            assert m["moves"] in e2e


def test_one_layer_name_per_layer():
    layers = {}
    for m in B["per_layer"]:
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_kernel_rooflines_have_a_step_mfu_beside_them():
    for m in B["per_layer"]:
        if "roofline" in m["name"]:
            assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                       for x in B["per_layer"])
