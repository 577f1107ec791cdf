"""The program's spans on the trace's clock: ``program_spans.reduce`` adds
``program`` and ``program_idle_gaps`` and leaves every key of
``trace_reduce.reduce`` as it was, and the two readers of ``program``."""

import json
import os

import pytest

import common
import program_spans
import run
import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_probe_trace.json")
MS = 1_000_000                  # ns


@pytest.fixture(scope="module")
def probe():
    with open(DATA) as f:
        return json.load(f)


def injected(trace):
    """The probe trace with program spans laid over its window: one outer
    span per ``bench.sleep`` with a child over its second half, and one
    collection inside the child."""
    spans = []
    for name, s, d in trace["spans"]:
        if name == "bench.sleep":
            spans += [["repro.serve.tick", s, d],
                      ["repro.serve.fetch", s + d / 2, d / 2],
                      ["repro.gc.collect.gen0", s + 3 * d / 4, d / 8]]
    return dict(trace, program_spans=spans)


def test_old_keys_unchanged_with_program_spans(probe):
    layers = {"heavy": ("probe_heavy",)}
    old = trace_reduce.reduce(probe, layers)
    new = program_spans.reduce(injected(probe), layers)
    assert set(new) == set(old) | {"program", "program_idle_gaps"}
    for key, value in old.items():
        assert new[key] == value, key
    bare = program_spans.reduce(probe, layers)
    assert bare["program"] == {}
    assert dict(bare["program_idle_gaps"]) == pytest.approx(
        dict(old["idle_gaps"]))


def test_program_on_probe(probe):
    out = program_spans.reduce(injected(probe), {})
    prog = out["program"]
    assert prog["repro.serve.tick"]["count"] == 3
    assert prog["repro.serve.fetch"]["s"] == pytest.approx(
        prog["repro.serve.tick"]["s"] / 2)
    gaps = dict(out["program_idle_gaps"])
    idle = out["window_s"] - out["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # The sleeps' idle time moves from the harness span to the program's.
    assert "bench.sleep" not in gaps
    sleep = dict(out["idle_gaps"])["bench.sleep"]
    charged = sum(v for k, v in gaps.items() if k.startswith("repro."))
    assert charged == pytest.approx(sleep, rel=1e-6)


def synthetic():
    """A 100 ms window; the device busy over [10, 20) and [60, 70) ms. Host:
    a harness span over [0, 100), a program tick over [5, 50) holding a
    fetch over [15, 40) that holds a collection over [30, 35), and a lone
    collection over [80, 90)."""
    return {
        "devices": [{"name": "/device:TPU:0",
                     "ops": [["a", 10 * MS, 10 * MS], ["b", 60 * MS, 10 * MS]],
                     "modules": [["jit_m", 10 * MS, 10 * MS],
                                 ["jit_m", 60 * MS, 10 * MS]]}],
        "spans": [["bench.window", 0, 100 * MS], ["bench.tick", 0, 100 * MS]],
        "program_spans": [["repro.serve.tick", 5 * MS, 45 * MS],
                          ["repro.serve.fetch", 15 * MS, 25 * MS],
                          ["repro.gc.collect.gen2", 30 * MS, 5 * MS],
                          ["repro.gc.collect.gen0", 80 * MS, 10 * MS]],
    }


def test_program_and_gaps_synthetic():
    out = program_spans.reduce(synthetic(), {})
    prog = out["program"]
    tick = prog["repro.serve.tick"]
    assert tick["count"] == 1
    assert tick["s"] == pytest.approx(0.045)
    assert tick["host_s"] == pytest.approx(0.035)       # 10 ms device busy
    assert prog["repro.serve.fetch"]["host_s"] == pytest.approx(0.020)
    assert prog["repro.gc.collect.gen2"]["max_s"] == pytest.approx(0.005)
    gaps = dict(out["program_idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(out["window_s"]
                                               - out["busy_s"])
    # Gaps: [0,10) -> tick covers 5 of 10; [20,60) -> fetch covers 20,
    # tick 30: the tick; [70,100) -> the lone collection covers 10.
    assert gaps == pytest.approx({"repro.serve.tick": 0.050,
                                  "repro.gc.collect.gen0": 0.030})
    assert dict(out["idle_gaps"]) == pytest.approx({"bench.tick": 0.080})


def test_innermost_span_takes_the_gap():
    trace = synthetic()
    # One device op ends at 31 ms and the next starts at 34 ms: the gap
    # [31, 34) lies inside the collection, the fetch and the tick alike.
    # No program span reaches the gap [95, 100): the harness span takes it.
    trace["devices"][0]["ops"] = [["a", 10 * MS, 21 * MS],
                                  ["b", 34 * MS, 61 * MS]]
    gaps = dict(program_spans.reduce(trace, {})["program_idle_gaps"])
    assert gaps == pytest.approx({"repro.serve.tick": 0.010,
                                  "repro.gc.collect.gen2": 0.003,
                                  "bench.tick": 0.005})


def test_program_spans_read_from_a_cpu_trace(tmp_path):
    import jax
    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.phase("serve.tick"):
            with obs.phase("serve.fetch"):
                pass
    finally:
        jax.profiler.stop_trace()
    spans = program_spans.program_spans(trace_reduce.find_xplane(
        str(tmp_path)))
    names = [n for n, _, _ in spans]
    assert "repro.serve.tick" in names and "repro.serve.fetch" in names
    (tick,) = [(s, d) for n, s, d in spans if n == "repro.serve.tick"]
    (fetch,) = [(s, d) for n, s, d in spans if n == "repro.serve.fetch"]
    assert tick[0] <= fetch[0] and fetch[0] + fetch[1] <= tick[0] + tick[1]


def read(name, reduced, counts=None):
    reader = common.load_module(
        os.path.join(program_spans.BENCH_DIR, "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))
    return reader.read(run.Reading(reduced, counts or {}, {}, {"name": name}))


def reduced_with(program):
    return {"window_s": 2.0, "busy_s": 0.1, "layers": {}, "modules": {},
            "device_ops": [], "idle_gaps": [], "program": program}


def entry(count, s, host_s=None, max_s=0.0):
    return {"count": count, "s": s, "host_s": s if host_s is None else host_s,
            "max_s": max_s}


@pytest.mark.parametrize("name", program_spans.READERS)
def test_readers_find_nothing_without_program_spans(name):
    reduced = reduced_with({})
    assert read(name, reduced) is None
    del reduced["program"]
    assert read(name, reduced) is None


def test_tick_host_ms_reader():
    out = read("tick_host_ms.pairs", reduced_with({
        "repro.serve.tick": entry(100, 0.25, host_s=0.24),
        "repro.serve.form": entry(100, 0.01),
        "repro.serve.group": entry(100, 0.02),
        "repro.serve.dispatch": entry(100, 0.05),
        "repro.serve.fetch": entry(100, 0.12),
        "repro.serve.respond": entry(100, 0.04)}))
    assert out["value"] == pytest.approx(2.4)
    assert out["waves"] == 100
    assert out["fetch"] == pytest.approx(1.2)
    assert out["unspanned"] == pytest.approx(0.1)
    assert sum(out[c] for c in ("form", "group", "dispatch", "fetch",
                                "respond", "unspanned")) == \
        pytest.approx(2.5)


def test_gc_pause_ms_reader():
    out = read("gc_pause_ms.pairs", reduced_with({
        "repro.serve.tick": entry(10, 0.02),
        "repro.gc.collect.gen0": entry(40, 0.004, max_s=0.0002),
        "repro.gc.collect.gen2": entry(1, 0.116, max_s=0.116)}))
    assert out["value"] == pytest.approx(60.0)          # 120 ms over 2 s
    assert out["collections"] == {"gen0": 40, "gen2": 1}
    assert out["longest_ms"] == pytest.approx({"gen0": 0.2, "gen2": 116.0})
    quiet = read("gc_pause_ms.pairs", reduced_with({
        "repro.serve.tick": entry(10, 0.02)}))
    assert quiet == {"value": 0.0, "collections": {}, "longest_ms": {}}
