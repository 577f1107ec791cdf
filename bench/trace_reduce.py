"""Reduce a profiler trace to device busy time, per-module device time and
idle gaps attributed to what the host was doing.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists; everything after it works on those lists, so the tests can feed it a
small recorded trace. Times are in nanoseconds on the trace's clock.

- Busy time is the union of the intervals in which a device operation ran
  ("XLA Ops" lines; the "XLA Modules" line where a device has no op line).
- A module's device time is the part of the busy union inside its module
  events, so a module that waits on the host inside its own span is not
  charged for the wait.
- An idle gap is a stretch of the window in no busy interval. It is charged
  to the innermost harness span (``bench.*``) that covers most of it, or to
  ``no_span``; gaps under 10 us lie between the ops of one program and are
  summed as ``between_ops``.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OP_GAP_NS = 10_000          # gaps shorter than this are between ops


def extract(xplane_path: str) -> Dict:
    """{"devices": [{"name", "ops": [[name, start, dur]], "modules": [...]}],
    "spans": [[name, start, dur]]} from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    # An op event's name is its whole HLO line; keep the
                    # instruction name before " = ".
                    dev[key].extend([e.name.split(" = ")[0], e.start_ns,
                                     e.duration_ns] for e in line.events)
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


class Cover:
    """Length of a disjoint sorted cover inside any interval, in log time."""

    def __init__(self, merged: Sequence[Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.prefix = [0.0]
        for a, b in merged:
            self.prefix.append(self.prefix[-1] + (b - a))

    def within(self, lo: float, hi: float) -> float:
        if hi <= lo or not self.starts:
            return 0.0
        i = bisect.bisect_right(self.ends, lo)      # first interval ending > lo
        j = bisect.bisect_left(self.starts, hi)     # intervals starting < hi
        if j <= i:
            return 0.0
        total = self.prefix[j] - self.prefix[i]
        total -= max(0.0, lo - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - hi)
        return total


def window_of(trace: Dict) -> Interval:
    """The harness's ``bench.window`` span, the traced window."""
    wins = [(s, s + d) for name, s, d in trace["spans"] if name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    return wins[0]


def device_busy(dev: Dict, lo: float, hi: float) -> List[Interval]:
    events = dev["ops"] or dev["modules"]
    return clip(union((s, s + d) for _, s, d in events), lo, hi)


def module_times(dev: Dict, busy: Sequence[Interval], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Device seconds of busy time inside each module's events, by name."""
    cover = Cover(busy)
    out: Dict[str, float] = {}
    for name, s, d in dev["modules"]:
        t = cover.within(max(s, lo), min(s + d, hi))
        if t > 0:
            out[name] = out.get(name, 0.0) + t * 1e-9
    return out


def layer_times(modules: Dict[str, float],
                layers: Dict[str, Sequence[str]]) -> Dict[str, float]:
    """Module seconds summed by layer. A module belongs to the first layer
    (in ``layers`` order) with a pattern that is a substring of its name;
    a module that matches none goes to ``other``."""
    out = {name: 0.0 for name in layers}
    out["other"] = 0.0
    for mod, t in modules.items():
        for name, patterns in layers.items():
            if any(p in mod for p in patterns):
                out[name] += t
                break
        else:
            out["other"] += t
    return out


def top_ops(dev: Dict, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` ops with the most device seconds inside the window, each
    named ``<module>/<op>`` after the module event that holds it."""
    mods = sorted((s, s + d, name.split("(")[0])
                  for name, s, d in dev["modules"])
    starts = [m[0] for m in mods]
    tot: Dict[str, float] = {}
    for name, s, d in dev["ops"] or dev["modules"]:
        t = min(s + d, hi) - max(s, lo)
        if t <= 0:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if dev["ops"] and i >= 0 and mods[i][1] >= s:
            name = f"{mods[i][2]}/{name}"
        tot[name] = tot.get(name, 0.0) + t * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(busy: Sequence[Interval], lo: float, hi: float,
              spans: Sequence[Sequence], n: int = 10) -> List[List]:
    """Idle seconds inside [lo, hi] by the host span that covers most of
    each gap (the shortest such span where several cover it equally).
    Gaps under ``OP_GAP_NS`` lie between the ops of one device program and
    are summed as ``between_ops``."""
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    host = sorted((s, s + d, name) for name, s, d in spans
                  if name != WINDOW_SPAN)
    tot: Dict[str, float] = {}
    for a, b in gaps:
        if b - a < OP_GAP_NS:
            name = "between_ops"
        else:
            best: Optional[Tuple[float, float, str]] = None
            for s, e, span in host:
                if s >= b:
                    break
                cover = min(b, e) - max(a, s)
                if cover > 0:
                    cand = (cover, -(e - s), span)
                    if best is None or cand > best:
                        best = cand
            name = best[2] if best else "no_span"
        tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: Dict, layers: Dict[str, Sequence[str]]) -> Dict:
    """Everything the per-layer readers and the breakdown need, averaged
    over the devices traced: busy and window seconds, seconds per layer and
    per module, and the top ops and idle gaps of the busiest device."""
    lo, hi = window_of(trace)
    devs = trace["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    per_dev = []
    for dev in devs:
        busy = device_busy(dev, lo, hi)
        per_dev.append((sum(b - a for a, b in busy), busy, dev))
    mods: Dict[str, float] = {}
    for _, busy, dev in per_dev:
        for k, v in module_times(dev, busy, lo, hi).items():
            mods[k] = mods.get(k, 0.0) + v / len(devs)
    _, busy, dev = max(per_dev, key=lambda x: x[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(x[0] for x in per_dev) / len(devs) * 1e-9,
        "modules": mods,
        "layers": layer_times(mods, layers),
        "device_ops": top_ops(dev, lo, hi),
        "idle_gaps": idle_gaps(busy, lo, hi, trace["spans"]),
    }
