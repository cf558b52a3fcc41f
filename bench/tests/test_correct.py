"""The comparison that decides `correct`, driven through a whole run at a
small size on the CPU (the harness's look for a chip skipped): sound runs
pass, the control fails, and each fault the serving cells can have,
planted under the timed path, makes `correct` false."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from bench import harness as H

CELLS = ["char-ptb.decode", "char-ptb.chat"]
SEED = 2**32 + 12345


def run(name, seed=SEED, **kw):
    return H.run_cell(name, seed, 2.0, False, t_start=time.perf_counter(),
                      require_chip=False, **kw)


def failed_by(r):
    return [k for k, c in r["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(small_cells, name):
    r = run(name)
    assert r["correct"] is True, r["checks"]
    assert r["readings"]["greedy_rows"] >= 1
    assert r["readings"]["slot_rows"] >= 1
    assert r["readings"]["greedy_tokens"] >= 50


def test_control_fails(monkeypatch):
    """The control, the reference with float32 products at `high` put in
    the program's place, comes out as not correct.  It is the reference
    alone, so it reads the same on any backend: the cell's own width
    (H=1000) with short requests."""
    from conftest import shrink_traffic

    traffic = H.SPEC.traffic
    monkeypatch.setattr(H.SPEC, "traffic", lambda n: shrink_traffic(traffic(n)))
    r = run("char-ptb.decode", control=True)
    assert r["correct"] is True
    ctl = {k: r["control"][k] <= c["limit"] for k, c in r["checks"].items()}
    assert not all(ctl.values()), (r["control"], r["checks"])


def test_fault_state_unchanged(small_cells, monkeypatch):
    from repro.serve.recurrent import RNNRuntime

    orig = RNNRuntime.decode_fn

    def frozen(self, tok, state, live=None, prm=None):
        logits, _ = orig(self, tok, state, live, prm)
        return logits, state

    monkeypatch.setattr(RNNRuntime, "decode_fn", frozen)
    r = run("char-ptb.decode")
    assert r["correct"] is False and failed_by(r)


def test_fault_half_the_batch_left_out(small_cells, monkeypatch):
    from repro.serve.recurrent import RNNRuntime

    orig = RNNRuntime.decode_fn

    def half(self, tok, state, live=None, prm=None):
        B = tok.shape[0]
        return orig(self, tok, state, live & (jnp.arange(B) < B // 2), prm)

    monkeypatch.setattr(RNNRuntime, "decode_fn", half)
    r = run("char-ptb.decode")
    assert r["correct"] is False and failed_by(r)


def test_fault_token_altered(small_cells, monkeypatch):
    import repro.serve.engine as E

    orig = E.sample_slots
    monkeypatch.setattr(
        E, "sample_slots",
        lambda logits, keys, **kw: (orig(logits, keys, **kw) + 1) % kw["vocab"])
    r = run("char-ptb.decode")
    assert r["correct"] is False and "greedy_gap" in failed_by(r)


def test_window_that_compiles_fails(small_cells, monkeypatch):
    orig = H.drive

    def drive(eng, w, *a, **k):
        if w.t0 <= time.perf_counter():     # inside the window
            eng.tick_traces += 1
        return orig(eng, w, *a, **k)

    monkeypatch.setattr(H, "drive", drive)
    with pytest.raises(H.RunFailed, match="compiled"):
        run("char-ptb.decode")


def _bench(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "char-ptb.decode",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            return "correct" not in json.loads(line)
        except ValueError:
            return True
    return True


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _bench(str(H.SPEC.ROOT), env)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "not on a TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    root = H.SPEC.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(root / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and _no_result(p.stdout)


def test_control_precisions():
    """The two controls the reference offers, against `highest`: three
    bfloat16 passes err by about 2^-16 of a product, one pass by 2^-8."""
    import jax

    from bench.models import bnlstm_reference as REF

    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (8, 1000))
    b = jax.random.normal(kb, (1000, 4000))
    ref = REF.dot_highest(a, b)
    scale = float(jnp.sqrt(jnp.mean(ref * ref)))
    err = {k: float(jnp.sqrt(jnp.mean((f(a, b) - ref) ** 2))) / scale
           for k, f in REF.CONTROLS.items()}
    assert 1e-7 < err["high"] < 1e-4
    assert 1e-3 < err["bfloat16"] < 1e-1
