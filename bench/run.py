"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``; the
mix names its driver module, which sets up (graph, tables, server, warm-up,
every shape the window uses), then drives the program for ``--seconds``
of host time, then checks what the window produced against the plain
references in ``bench/reference.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics. With
``--trace 1`` the profiler records the window, cut to the mix's
``trace_seconds``, and the result carries the per-layer metrics read from
that trace and from the driver module's counts, and the device's busy and
idle time.

The last line of standard output is one JSON object; its last key,
``checks``, holds each compared number beside its limit, and the same
numbers end standard error. The run exits non-zero, and prints no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import common  # noqa: E402
import trace_reduce  # noqa: E402


class Reading:
    """What a per-layer reader sees: the reduced trace, the window's counts
    and the device's peaks."""

    def __init__(self, reduced: Dict, counts: Dict, peaks: Dict,
                 metric: Dict):
        self.trace = reduced
        self.counts = counts
        self.peaks = peaks
        self.metric = metric

    def layer_s(self) -> float:
        return self.trace["layers"].get(self.metric["layer"], 0.0)


def device_info(chips: int):
    """The devices the cell runs on, or None (with the reason on stderr)
    where JAX finds no TPU or too few of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.log(f"needs a TPU; JAX found {devices[0].platform!r} "
                   f"({devices[0].device_kind})")
        return None
    if len(devices) < chips:
        common.log(f"the cell asks for {chips} chips; JAX found "
                   f"{len(devices)}")
        return None
    return devices[:chips]


def peaks_for(kind: str) -> Dict:
    table = common.load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def layer_patterns(cell: common.Cell) -> Dict[str, tuple]:
    """Layer name -> module-name patterns, from the readers' files."""
    out: Dict[str, tuple] = {}
    for m in cell.per_layer:
        mods = getattr(cell.reader(m["name"]), "MODULES", ())
        if mods:
            out[m["layer"]] = tuple(out.get(m["layer"], ())) + tuple(mods)
    return out


def per_layer_metrics(cell: common.Cell, reduced: Dict, counts: Dict,
                      peaks: Dict) -> Dict:
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"]).read(
            Reading(reduced, counts, peaks, m))
        if value is None:
            continue
        entry = value if isinstance(value, dict) else {"value": value}
        out[m["name"]] = {"value": entry.pop("value"), "unit": m["unit"],
                          **entry}
    return out


def run(cell: common.Cell, seed: int, seconds: float, traced: bool,
        devices, peaks: Optional[Dict]) -> Dict:
    """Set up, run the window, read memory, check; the result's fields."""
    import jax

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    window_s = seconds
    if traced:
        window_s = min(seconds, float(cell.traffic.get("trace_seconds",
                                                       seconds)))
    ctx = common.Context(cell=cell, seed=seed, seconds=window_s,
                         trace=traced)
    driver = cell.driver()
    state = driver.setup(ctx)
    # Set-up's garbage is collected before the window, not inside it.
    gc.collect()
    setup_s = time.perf_counter() - T_START
    compile_setup = compile_s[0]
    common.log(f"setup_s={setup_s} compile_s={compile_setup}")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(log_dir)
        try:
            with common.span("window"):
                result = driver.window(state, ctx)
        finally:
            if traced:
                jax.profiler.stop_trace()
        compile_window = compile_s[0] - compile_setup
        if compile_window > 0:
            common.log(f"WARNING: {compile_window} s of compilation inside "
                       "the window")
        peak = memory_peak(devices) if devices else 0
        reduced = None
        if traced:
            reduced = trace_reduce.reduce(
                trace_reduce.extract(trace_reduce.find_xplane(log_dir)),
                layer_patterns(cell))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    driver.release(state)
    checks: List[common.Check] = driver.check(state, ctx)

    if traced:
        metrics = per_layer_metrics(cell, reduced, result.counts, peaks)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                metrics[m["name"]] = {"value": result.metrics[m["name"]],
                                      "unit": m["unit"]}
    device = {
        "platform": devices[0].platform if devices else "cpu",
        "kind": devices[0].device_kind if devices else "cpu",
        "count": len(devices) if devices else 1,
        "memory_peak_bytes": peak,
    }
    out = {"correct": all(c.ok for c in checks) and bool(checks),
           "attempted": result.attempted, "failed": result.failed,
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
        common.log("device seconds by layer " + json.dumps(reduced["layers"]))
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    cell = common.resolve(args.workload)
    devices = device_info(cell.chips)
    if devices is None:
        return 2
    common.log(f"compile cache {common.enable_compile_cache()}")
    peaks = peaks_for(devices[0].device_kind)
    out = run(cell, args.seed, args.seconds, bool(args.trace), devices,
              peaks)
    for name, c in out["checks"].items():
        common.log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    common.log(f"correct={out['correct']}")
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
