"""The trace reduction on a small trace recorded once on a TPU v5e: three
rounds of a heavy and a light jitted program, each round followed by a
30 ms host sleep inside a ``bench.sleep`` span."""

import json
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_probe_trace.json")


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return json.load(f)


def brute_busy_ns(events, lo, hi, step=1000):
    """Busy time by sampling every microsecond of the window."""
    busy = 0
    for t in range(int(lo), int(hi), step):
        if any(s <= t < s + d for _, s, d in events):
            busy += step
    return busy


def test_busy_union_and_layer_map(trace):
    lo, hi = trace_reduce.window_of(trace)
    dev = trace["devices"][0]
    busy = trace_reduce.device_busy(dev, lo, hi)
    total = sum(b - a for a, b in busy)
    assert total == pytest.approx(brute_busy_ns(dev["ops"], lo, hi),
                                  rel=0.01)
    out = trace_reduce.reduce(trace, {"heavy": ("probe_heavy",)})
    assert out["busy_s"] == pytest.approx(total * 1e-9)
    assert out["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # Every busy second lies in one of the two modules: the heavy one by
    # its pattern, the light one in the other bucket.
    layers = out["layers"]
    assert set(layers) == {"heavy", "other"}
    assert layers["heavy"] + layers["other"] == pytest.approx(
        out["busy_s"], rel=1e-6)
    assert layers["heavy"] > 5 * layers["other"] > 0
    light = [k for k in out["modules"] if "probe_light" in k]
    assert layers["other"] == pytest.approx(out["modules"][light[0]])
    assert out["device_ops"][0][0].startswith("jit_probe_heavy/")


def test_gap_attribution(trace):
    out = trace_reduce.reduce(trace, {})
    gaps = dict(out["idle_gaps"])
    idle = out["window_s"] - out["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # Three 30 ms sleeps: the longest gaps are charged to them.
    assert max(gaps, key=gaps.get) == "bench.sleep"
    assert 0.09 <= gaps["bench.sleep"] <= idle


def test_cover_within_matches_clipping():
    merged = trace_reduce.union([(0, 10), (5, 20), (30, 40), (50, 60)])
    assert merged == [(0, 20), (30, 40), (50, 60)]
    cover = trace_reduce.Cover(merged)
    for lo, hi in [(0, 100), (15, 35), (35, 55), (41, 49), (-5, 3)]:
        want = sum(b - a for a, b in trace_reduce.clip(merged, lo, hi))
        assert cover.within(lo, hi) == want
