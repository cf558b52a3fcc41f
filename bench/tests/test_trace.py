"""The trace reducer, on a short window traced on a TPU v5e (char-ptb.decode,
8 slots; `data/char-ptb.decode.xplane.pb`, 50 ms)."""
from pathlib import Path

import pytest

from bench import peaks as PK
from bench import spec as SPEC
from bench import trace as TRC
from bench import work as WK
from bench.harness import Ctx

DATA = Path(__file__).parent / "data" / "char-ptb.decode.xplane.pb"


def test_names():
    assert TRC.op_name("%ternary_packed_gemv.6 = f32[8,4096]{1,0} "
                       "custom-call(f32[8,1024] %x)") == "ternary_packed_gemv"
    assert TRC.op_name("%copy-done.2 = f32[1,8,1000] copy-done(...)") == \
        "copy-done"
    assert TRC.op_name("fusion") == "fusion"
    assert TRC.module_name("jit_tick(7267081259785077133)") == "jit_tick"
    assert TRC.module_name("jit__lambda") == "jit__lambda"


@pytest.fixture(scope="module")
def summary():
    return TRC.summarize(str(DATA))


def test_busy_and_gaps_fill_the_window(summary):
    s = summary
    assert s.n_devices == 1
    assert 0.04 < s.window_s < 0.2
    assert 0 < s.busy_s < s.window_s
    idle = sum(v[0] for v in s.gaps.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)
    assert all(k.startswith("bench.") for k in s.gaps)
    assert len(s.top_gaps()) <= 10 and len(s.top_ops()) <= 10


def test_programs_and_kernels(summary):
    tick, n = summary.per_call_s("modules", lambda k: k == "jit_tick")
    kern, nk = summary.per_call_s("ops", lambda k: k.endswith("_decode_tick"))
    assert n > 10 and nk == n
    assert 20e-6 < kern <= tick < 2e-3
    assert summary.top_ops()[0][0] == "ternary_lstm_decode_tick"


def test_host_and_device_share_a_clock():
    """Every run of the tick program starts while the host is inside
    bench.step, the only span that dispatches it (to within 0.1 ms: the
    chip's clock is aligned to the host's, not identical)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA))
    steps, ticks = [], []
    for plane in pd.planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name == "bench.step":
                    steps.append((e.start_ns, e.start_ns + e.duration_ns))
                elif (plane.name.startswith("/device:TPU")
                      and ln.name == "XLA Modules"
                      and e.name.startswith("jit_tick(")):
                    ticks.append(e.start_ns)
    assert ticks and steps
    inside = sum(any(a <= t <= b + 1e5 for a, b in steps) for t in ticks)
    assert inside / len(ticks) > 0.9


def test_readers_on_the_trace(summary):
    cfg = SPEC.config("char-ptb")
    ticks = summary.modules["jit_tick"][1]
    ctx = Ctx(cfg=cfg, chips=1, slots=8, peak=PK.peaks("TPU v5 lite"),
              work=WK,
              trace=summary,
              host={"tokens": 8 * ticks, "ticks": ticks,
                    "tick_events": 8 * ticks, "prompt_tokens": 0,
                    "lags": []})
    read = {m["name"]: SPEC.metric_reader(m["name"])(ctx)
            for m in SPEC.benchmark()["per_layer"]}
    assert 0 < read["fused_tick_roofline.decode"] < 100
    assert 0 < read["mfu.decode"] < 1
    assert 0 < read["idle_share.decode"] < 100
    assert read["live_share.decode"] == pytest.approx(100.0)
    assert read["tick_device_us.decode"] > 20
    # nothing of prefill or of the generator in this window: no reading,
    # never a zero
    assert read["gen_lag_ms_p95.chat"] is None
    assert read["prefill_mfu.chat"] is None
    assert read["packed_gemv_roofline.chat"] is None
