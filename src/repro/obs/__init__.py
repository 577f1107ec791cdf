"""Unified runtime telemetry (DESIGN.md §13).

Four pieces behind one switch:

* ``metrics``  — process-wide registry of counters / gauges / bounded-
  window histograms (``inc`` / ``set_gauge`` / ``observe``);
* ``trace``    — ``phase`` and nested ``trace_span`` timing on the
  profiler's clock (``TraceAnnotation`` ``repro.<name>``), spans sharing
  fields with ``common.logging.log_context``, and the garbage-collector
  hook;
* ``recorder`` — bounded ring of recent spans/events, dumped to disk as
  a postmortem when a fault / divergence / retry path fails;
* ``export``   — Prometheus text snapshot + per-run RUN_TELEMETRY.json.

The whole substrate is host-side bookkeeping over scalars the runtime
already pulled: telemetry on vs off is bit-identical (property-tested),
and ``REPRO_TELEMETRY=0`` / ``configure(enabled=False)`` turns every
entry point into a flag check and removes the collector hook.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

from repro.obs.config import configure, emit_jsonl, enabled  # noqa: F401
from repro.obs.metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Reservoir,
    inc,
    observe,
    prometheus_snapshot,
    set_gauge,
    set_gauges,
)
from repro.obs.recorder import (  # noqa: F401
    dump_flight_record,
    load_flight_record,
    recent,
)
from repro.obs.trace import (  # noqa: F401
    ambient_fields,
    current_span,
    phase,
    span_event,
    span_stack,
    trace_span,
)
from repro.obs.export import (  # noqa: F401
    load_run_telemetry,
    run_telemetry,
    write_run_telemetry,
)

from repro.obs import config as _config
from repro.obs import recorder as _recorder
from repro.obs import trace as _trace

_trace.sync_collector_hook()


@contextlib.contextmanager
def override(**kwargs) -> Iterator[None]:
    """Temporarily reconfigure telemetry (tests / benches):

        with obs.override(enabled=False):
            ...  # telemetry fully off inside the block
    """
    prev = configure(**kwargs)
    try:
        yield
    finally:
        configure(enabled=prev["enabled"], clear_sinks=True)
        if prev["jsonl_path"]:
            configure(jsonl_path=prev["jsonl_path"])
        if prev["flight_dir"]:
            configure(flight_dir=prev["flight_dir"])


def reset() -> None:
    """Clear the registry and the flight-recorder ring (test isolation)."""
    REGISTRY.reset()
    _recorder.clear()
