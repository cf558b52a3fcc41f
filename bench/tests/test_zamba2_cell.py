"""The zamba2 cell's comparison, driven through a whole run at a small
size on the CPU (the same program path, dense fallback off the TPU): a
sound run is correct, the control (one bfloat16 pass) fails, and a tick
that drops its state update is caught."""
import time

import pytest

from bench import harness as H

SMALL = dict(hidden_size=64, n_mamba_heads=8, mamba_headdim=16,
             mamba_d_state=16, num_attention_heads=4, num_key_value_heads=4,
             attention_head_dim=32, intermediate_size=128, adapter_rank=8,
             vocab_size=256, vocab=256, num_hidden_layers=6,
             hybrid_layer_ids=[1, 3, 4])


@pytest.fixture
def small(monkeypatch):
    config, traffic = H.SPEC.config, H.SPEC.traffic

    def cfg(n):
        c = dict(config(n), **SMALL)
        c["deployment"] = dict(c["deployment"], slots_per_chip=4,
                               prefill_chunk=16)
        return c

    def mix(n):
        t = dict(traffic(n))
        t["prompt"] = dict(t["prompt"], min=8, median=40, max=96)
        t["output"] = dict(t["output"], min=8, median=32, max=64)
        return t

    monkeypatch.setattr(H.SPEC, "config", cfg)
    monkeypatch.setattr(H.SPEC, "traffic", mix)


def run(**kw):
    return H.run_cell("zamba2-7b.decode", 2**33 + 5, 3.0, False,
                      t_start=time.perf_counter(), require_chip=False, **kw)


def test_sound_run_and_control(small):
    r = run(control=True)
    assert r["correct"] is True, r["checks"]
    assert r["readings"]["greedy_tokens"] >= 50
    assert r["readings"]["slot_rows"] >= 1
    ctl = {k: r["control"][k] <= c["limit"] for k, c in r["checks"].items()}
    assert not all(ctl.values()), (r["control"], r["checks"])


def test_fault_state_unchanged(small, monkeypatch):
    from repro.serve.recurrent import TransformerRuntime

    orig = TransformerRuntime.decode_fn

    def frozen(self, tok, state, live=None, prm=None):
        logits, _ = orig(self, tok, state, live, prm)
        return logits, state

    monkeypatch.setattr(TransformerRuntime, "decode_fn", frozen)
    r = run()
    assert r["correct"] is False
