import numpy as np
import pytest

from bench import spec as SPEC
from bench import traffic as TR

MIXES = ["decode-char", "chat-char"]


def _key(reqs):
    return [(r.prompt.tolist(), r.max_tokens, r.temperature, r.top_k, r.seed)
            for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    tp = SPEC.traffic(mix)
    seed = 2**33 + 17
    if tp["loop"] == "closed":
        a, b = TR.closed_pool(tp, seed, 50), TR.closed_pool(tp, seed, 50)
        c = TR.closed_pool(tp, seed + 1, 50)
    else:
        # the requests due inside the window
        a, b, c = ([r for t, r in TR.open_schedule(tp, s, 50, 20.0)
                    if 0.0 <= t < 20.0] for s in (seed, seed, seed + 1))
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # another seed reorders the same lengths: the work does not change
    assert sorted(r.max_tokens for r in a) == sorted(r.max_tokens for r in c)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in c)


@pytest.mark.parametrize("mix", MIXES)
def test_stated_distributions(mix):
    tp = SPEC.traffic(mix)
    if tp["loop"] == "closed":
        reqs = TR.closed_pool(tp, 5, 50)
    else:
        reqs = [r for _, r in TR.open_schedule(tp, 5, 50, 200.0)]
    for key, vals in (("prompt", [r.prompt.size for r in reqs]),
                      ("output", [r.max_tokens for r in reqs])):
        d = tp[key]
        assert min(vals) >= d["min"] and max(vals) <= d["max"]
        med = float(np.median(vals))
        want = d["median"] if d["dist"] == "lognormal" else \
            (d["min"] + d["max"]) / 2
        assert abs(med - want) <= 0.03 * want + 1
    every = tp["sampling"]["greedy_every"]
    greedy = [r for r in reqs if r.greedy]
    assert abs(len(greedy) - len(reqs) / every) <= 1
    assert all(r.temperature == 0.0 and r.top_k == 0 for r in greedy)
    assert all(r.temperature == tp["sampling"]["temperature"]
               for r in reqs if not r.greedy)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50 for r in reqs)


def test_lognormal_quantiles():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128,
         "max": 4096}
    assert TR.quantile(d, 0.5) == 512
    # the 84th percentile of a lognormal is median * e^sigma
    assert abs(TR.quantile(d, 0.8413) - 512 * np.exp(0.7)) < 3
    assert TR.quantile(d, 1e-6) == 128 and TR.quantile(d, 1 - 1e-9) == 4096


def test_open_schedule_bursts():
    tp = SPEC.traffic("chat-char")
    sched = TR.open_schedule(tp, 9, 50, 20.0)
    due = np.array([t for t, _ in sched])
    assert np.all(np.diff(due) >= 0)
    phase = tp["bursts"]["phase_s"]
    lead = tp["lead_in_s"]
    factors = tp["bursts"]["factors"]
    for k in range(int((lead + 20.0) / phase)):
        start = -lead + k * phase
        f = factors[int(round(start / phase)) % len(factors)]
        n = np.sum((due >= start) & (due < start + phase))
        assert abs(n - tp["rate_per_s"] * f * phase) <= 1
    window = np.sum((due >= 0) & (due < 20.0))
    assert window == round(tp["rate_per_s"] * 20.0)


def test_preroll_staggers_the_slots():
    tp = SPEC.traffic("decode-char")
    pre = TR.preroll(tp, 3, 50, 8)
    outs = [r.max_tokens for r in pre]
    assert outs == sorted(outs) and len(set(outs)) == 8
    assert outs[-1] == TR.quantile(tp["output"], 0.5)


def test_open_schedule_rotates_one_pattern():
    """Every seed's window holds the same arrivals with the same lengths, up
    to a shift by whole burst cycles; the pattern repeats around it."""
    tp = SPEC.traffic("chat-char")
    cyc = tp["bursts"]["phase_s"] * len(tp["bursts"]["factors"])
    secs = 5 * cyc

    def window(seed, k=0):
        return sorted((round((t + k * cyc) % secs, 9), r.prompt.size,
                       r.max_tokens, r.greedy)
                      for t, r in TR.open_schedule(tp, seed, 50, secs)
                      if 0.0 <= t < secs)

    base = window(1)
    shifts = set()
    for seed in (2, 2**33 + 5, 77, 78, 79):
        ks = [k for k in range(5) if window(seed, k) == base]
        assert len(ks) == 1
        shifts.add(ks[0])
    assert len(shifts) > 1
    s = TR.open_schedule(tp, 3, 50, secs)
    by_t = {round(t, 9): (r.prompt.size, r.max_tokens) for t, r in s}
    for t, r in s:
        if t >= secs:
            assert by_t[round(t - secs, 9)] == (r.prompt.size, r.max_tokens)
