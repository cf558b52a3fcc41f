"""The zamba2 cell's counts against its shapes, and its per-layer readers
on a made-up trace."""
import types

import pytest

from bench import peaks as PK
from bench import spec as SPEC
from bench.models import zamba2_work as W


@pytest.fixture
def cfg():
    return SPEC.config("zamba2-7b")


def test_matrices_and_flops_at_published_widths(cfg):
    mats = W.matrices(cfg)
    assert len(mats) == 2 * 81 + 9 * 13
    assert mats[:2] == [(3584, 14704), (7168, 3584)]
    # 81 mamba layers, 13 calls of q, k, v (7168 -> 7168), o, gate_up
    # (3584 -> 28672), down, the adapter pair and the linear
    block = (3 * 7168 * 7168 + 7168 * 3584 + 3584 * 28672 + 14336 * 3584)
    own = 3584 * 128 + 128 * 28672 + 3584 * 3584
    mamba = 81 * (3584 * 14704 + 7168 * 3584)
    # held: two blocks, 13 calls' own matrices; 7.24 B, 1.81 GB of codes
    assert W.quantized_params(cfg) == mamba + 2 * block + 13 * own
    assert round(W.quantized_params(cfg) / 1e9, 2) == 7.24
    assert round(W.code_bytes(cfg) / 1e9, 2) == 1.81
    # passed through: a block at each of its calls; 22.1 GFLOP a token
    # with the tied head (2 x 7.35 B = 14.7 would count a block once)
    assert sum(k * n for k, n in W.matrices(cfg)) == mamba + 13 * (block + own)
    assert W.model_flops_per_token(cfg) == 2 * (mamba + 13 * (block + own)
                                                + 3584 * 32000)
    assert round(W.model_flops_per_token(cfg) / 1e9, 1) == 22.1


def test_state_bytes_and_least_times(cfg):
    h = 81 * 8 * 112 * 64 * 64 * 4 * 2          # read and written
    conv = 81 * 8 * 3 * 7424 * 4 * 2
    kv = 13 * 2640 * 7168 * 4 * 2               # 8 slots at 330 tokens
    assert W.state_bytes(cfg, 8, 2640) == h + conv + kv
    assert round(W.state_bytes(cfg, 8, 2640) / 1e9, 2) == 4.46
    peak = PK.peaks("TPU v5 lite")
    least = W.tick_gemv_least_s(cfg, 8, peak)
    # bytes bound every matrix at 8 rows: codes plus activations
    assert least == pytest.approx(sum(
        (k * n / 4 + 8 * (k + n) * 4) / 819e9 for k, n in W.matrices(cfg)))
    assert 3.4e-3 < least < 3.6e-3          # 2.7 GB of codes a tick


def test_readers_on_a_made_up_trace(cfg, monkeypatch):
    from repro.serve import telemetry
    n_mats = len(W.matrices(cfg))
    summ = types.SimpleNamespace(
        window_s=8.0, busy_s=6.0,
        per_call_s=lambda table, match: {
            ("modules", True): (0.1, 50), ("ops", True): (1e-4, 50 * n_mats),
        }[(table, bool(match("jit_tick") or match("ternary_packed_gemv")
                       or match("jit_prefill_slot")))])
    ctx = types.SimpleNamespace(cfg=cfg, chips=1, slots=8,
                                peak=PK.peaks("TPU v5 lite"), trace=summ,
                                host={"tokens": 400, "prompt_tokens": 900})
    monkeypatch.setattr(telemetry, "snapshot", lambda: {
        "spans": {"serve.tick": {"n": 50, "total_s": 5.0, "self_s": 5.0}},
        "counts": {"serve.live_context": 50 * 2640, "serve.prefill_pad": 100},
        "queue_s": []})
    read = lambda name: SPEC.metric_reader(name)(ctx)
    assert read("tick_device_us.zamba2") == pytest.approx(1e5)
    assert read("idle_share.zamba2") == pytest.approx(25.0)
    assert read("mfu.zamba2") == pytest.approx(
        100 * 400 * W.model_flops_per_token(cfg) / (8 * 197e12))
    gemv = read("packed_gemv_roofline.zamba2")
    assert gemv == pytest.approx(100 * W.tick_gemv_least_s(
        cfg, 8, ctx.peak) / (1e-4 * n_mats))
    state = read("state_roofline.zamba2")
    # over the tick less its GEMV launches: 0.1 s - 279 x 0.1 ms
    assert state == pytest.approx(100 * W.state_bytes(cfg, 8, 2640) / 819e9
                                  / (0.1 - 1e-4 * n_mats))
    assert read("prefill_pad_share.zamba2") == pytest.approx(10.0)
