"""The program's own spans on the profiler's clock: where the host's time
went while the device sat idle.

``repro.obs`` holds a ``TraceAnnotation`` named ``repro.<name>`` open around
each of the program's phases and spans, and one named
``repro.gc.collect.gen<g>`` around each collection of Python's garbage
collector. ``trace_reduce`` keeps only the harness's ``bench.*`` spans; this
module reads the ``repro.*`` ones from the same trace and adds two keys to
``trace_reduce.reduce``'s result, leaving every other key as it is:

- ``program``: for each span name, ``{count, s, host_s, max_s}`` inside the
  ``bench.window`` span, where ``host_s`` is the part of the span that no
  device operation covers;
- ``program_idle_gaps``: the device's idle time by the rule of
  ``trace_reduce.idle_gaps``, charged to the innermost program span that
  covers most of each gap, else to the harness span, else to ``no_span``.

The readers ``metrics/tick_host_ms.pairs.py`` and
``metrics/gc_pause_ms.pairs.py`` read ``program``. ``bench/run.py`` does not
call this module yet (PERF.md, open questions); run as a script, it runs one
cell's window under the profiler as ``bench/run.py --trace 1`` does and
prints the result with both readers' values:

    python3 bench/program_spans.py --workload lj-sim.pairs --seed <n> [--trace 0]

With ``--trace 0`` no profiler runs, and the result holds the program's own
histograms for the window instead. For a serving cell it then also times
``tick()`` with telemetry on and off, in blocks of waves taken in turn.
"""

from __future__ import annotations

import bisect
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "repro."
READERS = ("tick_host_ms.pairs", "gc_pause_ms.pairs")

Span = Tuple[float, float, str]      # start, end, name


def program_spans(xplane_path: str) -> List[List]:
    """[[name, start, dur]] of the program's host events in one
    ``.xplane.pb``, on the clock of ``trace_reduce.extract``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.duration_ns]
                           for e in line.events
                           if e.name.startswith(PROGRAM_PREFIX))
    return out


def program(spans: Sequence[Sequence], busy: Sequence[Tuple[float, float]],
            lo: float, hi: float) -> Dict[str, Dict]:
    """Seconds inside [lo, hi] of each program span name, and the part of
    them that no busy interval covers."""
    cover = trace_reduce.Cover(busy)
    out: Dict[str, Dict] = {}
    for name, s, d in spans:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        e = out.setdefault(name, {"count": 0, "s": 0.0, "host_s": 0.0,
                                  "max_s": 0.0})
        e["count"] += 1
        e["s"] += (b - a) * 1e-9
        e["host_s"] += (b - a - cover.within(a, b)) * 1e-9
        e["max_s"] = max(e["max_s"], (b - a) * 1e-9)
    return out


class _Covering:
    """The span that covers most of an interval, the shortest among equals:
    the innermost one where spans nest."""

    def __init__(self, spans: Sequence[Sequence]):
        self.spans: List[Span] = sorted((s, s + d, name)
                                        for name, s, d in spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0.0)

    def best(self, a: float, b: float) -> Optional[str]:
        found: Optional[Tuple[float, float, str]] = None
        i = bisect.bisect_left(self.starts, a - self.longest)
        for s, e, name in self.spans[i:]:
            if s >= b:
                break
            cover = min(b, e) - max(a, s)
            if cover > 0:
                cand = (cover, -(e - s), name)
                if found is None or cand > found:
                    found = cand
        return found[2] if found else None


def program_idle_gaps(busy: Sequence[Tuple[float, float]], lo: float,
                      hi: float, program_spans_: Sequence[Sequence],
                      bench_spans: Sequence[Sequence], n: int = 10
                      ) -> List[List]:
    """Idle seconds inside [lo, hi] by the program span that covers most of
    each gap, else the harness span, else ``no_span``; gaps under
    ``trace_reduce.OP_GAP_NS`` are ``between_ops``."""
    inner = _Covering(program_spans_)
    outer = _Covering([x for x in bench_spans
                       if x[0] != trace_reduce.WINDOW_SPAN])
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    tot: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < trace_reduce.OP_GAP_NS:
            name = "between_ops"
        else:
            name = inner.best(a, b) or outer.best(a, b) or "no_span"
        tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Dict, layers: Dict[str, Sequence[str]]) -> Dict:
    """``trace_reduce.reduce`` plus ``program`` and ``program_idle_gaps``
    from ``trace["program_spans"]`` (none if the key is absent), on the
    busiest device's busy time."""
    out = trace_reduce.reduce(trace, layers)
    lo, hi = trace_reduce.window_of(trace)
    busy = max((trace_reduce.device_busy(dev, lo, hi)
                for dev in trace["devices"]),
               key=lambda b: sum(e - s for s, e in b))
    spans = trace.get("program_spans", [])
    out["program"] = program(spans, busy, lo, hi)
    out["program_idle_gaps"] = program_idle_gaps(busy, lo, hi, spans,
                                                 trace["spans"])
    return out


# ---------------------------------------------------------------------------
# The script: one cell's window, traced or not
# ---------------------------------------------------------------------------

def _histograms(names: Sequence[str]) -> Dict[str, Tuple[int, float]]:
    from repro import obs

    snap = obs.REGISTRY.snapshot()["histograms"]
    return {n: (snap.get(n, {}).get("count", 0), snap.get(n, {}).get("sum",
                                                                      0.0))
            for n in names}


def tick_cost(server, num_nodes: int, candidates: int, reads: int,
              blocks: int, waves: int, seed: int) -> Dict:
    """Median host seconds of one ``tick()`` of ``reads`` pair reads with
    telemetry on and with it off, over ``blocks`` blocks of ``waves`` waves
    on each side, the sides taken in turn, and their difference."""
    import time

    import numpy as np
    from repro import obs

    rng = np.random.default_rng(seed)
    walls = {True: [], False: []}
    prev = obs.enabled()
    try:
        for block in range(2 * blocks):
            on = block % 2 == 0
            obs.configure(enabled=on)
            for _ in range(waves):
                for u in rng.integers(0, num_nodes, reads):
                    server.submit(int(u), rng.integers(
                        0, num_nodes, candidates, dtype=np.int32))
                t0 = time.perf_counter()
                server.tick()
                walls[on].append(time.perf_counter() - t0)
    finally:
        obs.configure(enabled=prev)
    on_s = float(np.median(walls[True]))
    off_s = float(np.median(walls[False]))
    return {"reads_per_wave": reads, "waves_each": blocks * waves,
            "on_ms": on_s * 1e3, "off_ms": off_s * 1e3,
            "added_us": (on_s - off_s) * 1e6}


def main(argv=None) -> int:
    import argparse
    import gc
    import json
    import shutil
    import tempfile

    import common
    import run

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = p.parse_args(argv)

    cell = common.resolve(args.workload)
    devices = run.device_info(cell.chips)
    if devices is None:
        return 2
    common.enable_compile_cache()
    peaks = run.peaks_for(devices[0].device_kind)
    import jax

    seconds = float(cell.traffic.get("trace_seconds", 10))
    ctx = common.Context(cell=cell, seed=args.seed, seconds=seconds,
                         trace=bool(args.trace))
    driver = cell.driver()
    state = driver.setup(ctx)
    gc.collect()
    names = ["span.serve.tick.s"] + [f"span.serve.{c}.s" for c in (
        "form", "group", "dispatch", "fetch", "respond")]
    before = _histograms(names)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        if args.trace:
            jax.profiler.start_trace(log_dir)
        try:
            with common.span("window"):
                result = driver.window(state, ctx)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        after = _histograms(names)
        out: Dict = {"workload": cell.name, "seed": args.seed,
                     "trace": args.trace, "counts": result.counts,
                     "metrics": result.metrics,
                     "device": devices[0].device_kind}
        # The program's own histograms over the window, drain included.
        out["histograms_ms"] = {
            n: 1e3 * (after[n][1] - before[n][1])
            / max(after[n][0] - before[n][0], 1) for n in names}
        out["histogram_waves"] = after[names[0]][0] - before[names[0]][0]
        if args.trace:
            path = trace_reduce.find_xplane(log_dir)
            trace = trace_reduce.extract(path)
            trace["program_spans"] = program_spans(path)
            reduced = reduce(trace, run.layer_patterns(cell))
            out["reduced"] = {k: v for k, v in reduced.items()
                              if k != "modules"}
            out["readers"] = {}
            for name in READERS:
                reader = common.load_module(
                    os.path.join(BENCH_DIR, "metrics", name + ".py"),
                    "bench_metric_" + name.replace(".", "_"))
                out["readers"][name] = reader.read(run.Reading(
                    reduced, result.counts, peaks, {"name": name}))
        server = getattr(state, "server", None)
        if not args.trace and server is not None and "candidates" in \
                cell.traffic:
            reads = max(1, round(result.counts.get("reads", 0)
                                 / max(result.counts.get("waves", 1), 1)))
            out["tick_cost"] = tick_cost(
                server, int(cell.config["graph"]["num_nodes"]),
                int(cell.traffic["candidates"]), reads, blocks=40,
                waves=50, seed=args.seed)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    driver.release(state)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
