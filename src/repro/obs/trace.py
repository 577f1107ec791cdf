"""Span tracer: nested, contextvar-scoped phase timing on the profiler's
clock.

Two kinds of timed region share one clock:

* ``with phase("serve.tick"):`` — the light one, for per-wave and
  per-chunk sites: the body's wall time in the ``span.serve.tick.s``
  histogram and a ``jax.profiler.TraceAnnotation`` named
  ``repro.serve.tick`` held open around the body. Nothing else: no
  record, no fields, no span-stack frame.
* ``with trace_span("walk.round", round=r):`` — a phase plus the
  bookkeeping a postmortem needs, for rare lifecycle sites (round,
  checkpoint, refresh, offer, ingest).

The annotation is made only while a profiler session records (one check,
about 40 ns, otherwise); under ``jax.profiler.start_trace`` it lands on
the host plane of the trace, beside the device's ops, so idle device
time can be charged to the program's own phases.

While telemetry is on, one ``gc.callbacks`` hook times every collection
of Python's garbage collector as an annotation
``repro.gc.collect.gen<g>`` and adds it to the registry's per-generation
totals (counters ``gc.collections.gen<g>``, ``gc.pause_s.gen<g>``).

A span's wall time lands in the ``span.walk.round.s`` histogram, the
closed-span record is appended to the flight recorder ring and the JSONL
event stream, and — because the span body runs inside
``common.logging.log_context(**fields)`` — every log line emitted inside
the span carries the span's fields. Spans nest: a child records its
parent's name, and ``current_span()`` exposes the innermost frame so
point events (``span_event``) can attach to it.

Thread isolation comes free from the contextvar: a prefetch thread
starts with an empty span stack and cannot corrupt the driver thread's
nesting (property-tested in tests/test_obs.py).

The tracer is host-side only and time-based only — it never touches
device values, so it cannot perturb compiled computations. With
telemetry disabled ``phase`` and ``trace_span`` cost one flag check (no
clock reads, no annotation, no contextvar writes) and no collector hook
is installed.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import time
from typing import Any, Dict, Iterator, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.common.logging import current_context_fields, get_logger, \
    log_context
from repro.obs import config as _config
from repro.obs import metrics as _metrics

_log = get_logger("repro.obs")

_SPAN_STACK: contextvars.ContextVar[Tuple[Dict[str, Any], ...]] = (
    contextvars.ContextVar("repro_span_stack", default=()))

#: Monotonically-increasing span id (uniqueness only; no ordering claims
#: across threads).
_NEXT_ID = [0]


def current_span() -> Optional[Dict[str, Any]]:
    """The innermost open span frame in this thread/context, or None."""
    stack = _SPAN_STACK.get()
    return stack[-1] if stack else None


def span_stack() -> Tuple[Dict[str, Any], ...]:
    """The full open-span stack (outermost first)."""
    return _SPAN_STACK.get()


def ambient_fields() -> Dict[str, Any]:
    """Merged fields of every open span, outer→inner (inner wins).

    This is what the flight recorder stamps onto point events so a
    fault fired deep inside ``refresh.splice`` still carries the round
    and graph_version of the enclosing spans.
    """
    fields: Dict[str, Any] = {}
    for frame in _SPAN_STACK.get():
        fields.update(frame["fields"])
    return fields


class phase:
    """Time the body as one region on the profiler's clock::

        with obs.phase("serve.fetch"):
            host = np.asarray(scores)

    Records the body's wall time in ``span.<name>.s`` and, while a
    profiler session records, holds a ``TraceAnnotation`` named
    ``repro.<name>`` open around it. ``wall_s`` holds the time after the
    body (None with telemetry off).
    """

    __slots__ = ("name", "wall_s", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.wall_s: Optional[float] = None
        self._annotation = None
        self._t0: Optional[float] = None

    def __enter__(self) -> "phase":
        if _config.enabled():
            if TraceAnnotation.is_enabled():
                self._annotation = TraceAnnotation("repro." + self.name)
                self._annotation.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        if self._t0 is not None:
            self.wall_s = time.perf_counter() - self._t0
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
            _metrics.REGISTRY.histogram(
                "span." + self.name + ".s").observe(self.wall_s)
        return False


@contextlib.contextmanager
def trace_span(name: str, **fields: Any) -> Iterator[Optional[Dict[str, Any]]]:
    """Open a named span around the body: a ``phase`` plus a frame.

    On exit (normal or exceptional) the closed-span record goes to the
    flight recorder and the JSONL stream, and the duration is recorded
    in the ``span.<name>.s`` histogram. An exception marks the record
    ``ok=False`` with the error type, then propagates.
    """
    if not _config.enabled():
        yield None
        return
    _NEXT_ID[0] += 1
    stack = _SPAN_STACK.get()
    frame: Dict[str, Any] = {
        "kind": "span",
        "id": _NEXT_ID[0],
        "name": name,
        "parent": stack[-1]["name"] if stack else None,
        "fields": dict(fields),
        "t_start": time.time(),
        "depth": len(stack),
    }
    token = _SPAN_STACK.set(stack + (frame,))
    timed = phase(name)
    try:
        with timed, log_context(**fields):
            yield frame
        frame["ok"] = True
    except BaseException as e:
        frame["ok"] = False
        frame["error"] = type(e).__name__
        raise
    finally:
        frame["wall_s"] = timed.wall_s
        _SPAN_STACK.reset(token)
        from repro.obs import recorder as _recorder
        _recorder.record(frame)
        # Spans share the structured-log formatter: the close line runs
        # inside the span's own log_context so it carries the fields.
        if _log.isEnabledFor(10):  # logging.DEBUG
            with log_context(**fields):
                _log.debug("span %s wall=%.6fs ok=%s", name,
                           frame["wall_s"], frame.get("ok"))


def span_event(name: str, **fields: Any) -> None:
    """Record a point event (no duration) attached to the current span.

    Events land in the flight recorder and JSONL stream stamped with the
    merged fields of every enclosing span AND the ambient ``log_context``
    frames, so ``span_event("heal", reason=...)`` inside ``walk.round``
    carries the round for free — and a ``log_context(shard=...)`` block
    (no span) still stamps the shard.
    """
    if not _config.enabled():
        return
    record = {
        "kind": "event",
        "name": name,
        "t": time.time(),
        "fields": {**current_context_fields(), **ambient_fields(),
                   **fields},
        "span": (current_span() or {}).get("name"),
    }
    from repro.obs import recorder as _recorder
    _recorder.record(record)


class _CollectorHook:
    """The ``gc.callbacks`` entry: each collection becomes an annotation
    ``repro.gc.collect.gen<g>`` and a count and pause in
    ``REGISTRY.gc``. It takes no lock (see ``metrics.CollectorTotals``);
    collections never overlap, since the collecting thread holds the
    interpreter."""

    NAMES = tuple(f"repro.gc.collect.gen{g}" for g in range(3))

    def __init__(self):
        self._annotation = None
        self._t0: Optional[float] = None

    def __call__(self, stage: str, info: Dict[str, Any]) -> None:
        g = info["generation"]
        if stage == "start":
            if TraceAnnotation.is_enabled():
                self._annotation = TraceAnnotation(self.NAMES[g])
                self._annotation.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            pause = time.perf_counter() - self._t0
            self._t0 = None
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            totals = _metrics.REGISTRY.gc
            totals.collections[g] += 1
            totals.pause_s[g] += pause


_COLLECTOR_HOOK = _CollectorHook()


def sync_collector_hook() -> None:
    """Install the collector hook while telemetry is on, remove it while
    off (``configure`` calls this on every change)."""
    installed = _COLLECTOR_HOOK in gc.callbacks
    if _config.enabled() and not installed:
        gc.callbacks.append(_COLLECTOR_HOOK)
    elif not _config.enabled() and installed:
        gc.callbacks.remove(_COLLECTOR_HOOK)
