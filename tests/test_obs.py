"""Unified runtime telemetry (DESIGN.md §13).

Four contracts under test:

* the metrics/tracer/recorder substrate itself — bounded reservoirs,
  span nesting, contextvar isolation across the prefetch thread, the
  log_context integration, and the logging-config satellite fixes;
* flight-recorder postmortems — a chaos-injected crash (``wal_append``,
  ``refresh_splice``) must dump a record whose faulting span carries its
  round/shard/graph_version fields;
* RUN_TELEMETRY.json — schema round-trip and validation;
* the non-negotiable invariant: telemetry fully on vs fully off is
  BIT-IDENTICAL in phi and the corpus ring — for a plain run, across a
  divergence heal (lr_backoff=1.0), and across a crash-resume.
"""

import dataclasses
import gc
import glob
import json
import logging
import os
import threading

import numpy as np
import pytest

from repro import obs
from repro.common.logging import get_logger, log_context, refresh_log_level
from repro.core.api import EmbedConfig, make_walk_plan
from repro.core.dsgl import DSGLConfig
from repro.graph.delta import EdgeBatch
from repro.graph.generators import rmat_graph
from repro.obs import config as obs_config
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs import trace as obs_trace
from repro.runtime.faults import (FaultInjector, SimulatedFailure,
                                  run_with_restarts)
from repro.runtime.health import HealthConfig, HealthMonitor
from repro.runtime.ingest import IngestConfig, IngestDriver
from repro.runtime.trainer import StreamingEmbedPipeline


def _plan(seed=3, dim=16):
    cfg = dataclasses.replace(EmbedConfig(dim=dim, seed=seed),
                              rng_mode="vertex")
    policy, spec, rounds = make_walk_plan(cfg)
    return policy, spec, rounds, DSGLConfig(dim=dim, seed=seed)


def _pipeline(graph, **kw):
    policy, spec, rounds, dsgl = _plan()
    return StreamingEmbedPipeline(graph, policy, spec, rounds, dsgl, **kw)


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(128, 7, seed=7)


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.reset()
    obs.configure(enabled=True, clear_sinks=True)
    yield
    obs.reset()
    obs.configure(enabled=True, clear_sinks=True)


# --- metrics registry -------------------------------------------------------


class TestMetrics:
    def test_counter_gauge(self):
        obs.inc("x.count")
        obs.inc("x.count", 2.5)
        obs.set_gauge("x.g", 7)
        snap = obs.REGISTRY.snapshot()
        assert snap["counters"]["x.count"] == 3.5
        assert snap["gauges"]["x.g"] == 7.0

    def test_histogram_window_is_bounded(self):
        h = obs.REGISTRY.histogram("x.h", window=8)
        for v in range(100):
            h.observe(v)
        assert len(h.values()) == 8
        assert h.count == 100                      # lifetime count survives
        assert h.min == 0 and h.max == 99
        # Window percentiles are np.percentile over the LAST 8 values.
        assert h.percentile(50) == pytest.approx(
            np.percentile(np.arange(92, 100), 50))

    def test_empty_histogram(self):
        h = obs_metrics.Histogram("empty")
        assert h.percentile(50) is None
        assert h.summary() == {"count": 0}

    def test_disabled_is_noop(self):
        with obs.override(enabled=False):
            obs.inc("gone")
            obs.set_gauge("gone.g", 1)
            obs.observe("gone.h", 1.0)
        snap = obs.REGISTRY.snapshot()
        assert "gone" not in snap["counters"]
        assert "gone.g" not in snap["gauges"]
        assert "gone.h" not in snap["histograms"]

    def test_prometheus_snapshot(self):
        obs.inc("walk.supersteps", 41)
        obs.set_gauge("walk.pool_slots", 256)
        obs.observe("span.walk.round.s", 0.25)
        text = obs.prometheus_snapshot()
        assert "# TYPE repro_walk_supersteps counter" in text
        assert "repro_walk_supersteps 41" in text
        assert "repro_walk_pool_slots 256" in text
        assert 'repro_span_walk_round_s{quantile="0.50"} 0.25' in text

    def test_attach_shares_driver_owned_histogram(self):
        h = obs_metrics.Histogram(window=4)
        obs.REGISTRY.attach("ingest.latency_s", h)
        h.observe(1.0)
        snap = obs.REGISTRY.snapshot()
        assert snap["histograms"]["ingest.latency_s"]["count"] == 1


# --- span tracer ------------------------------------------------------------


class TestTracer:
    def test_nesting_and_recorder_order(self):
        with obs.trace_span("outer", round=1) as f_out:
            with obs.trace_span("inner", shard=2) as f_in:
                assert f_in["parent"] == "outer"
                assert f_in["depth"] == 1
                assert obs.ambient_fields() == {"round": 1, "shard": 2}
            assert obs.current_span() is f_out
        assert obs.current_span() is None
        names = [r["name"] for r in obs.recent()]
        assert names == ["inner", "outer"]         # closed inner-first
        snap = obs.REGISTRY.snapshot()
        assert snap["histograms"]["span.outer.s"]["count"] == 1
        assert snap["histograms"]["span.inner.s"]["count"] == 1

    def test_span_error_marked_and_propagated(self):
        with pytest.raises(ValueError):
            with obs.trace_span("boom"):
                raise ValueError("x")
        rec = obs.recent()[-1]
        assert rec["ok"] is False and rec["error"] == "ValueError"

    def test_span_event_inherits_ambient_fields(self):
        with log_context(shard=3):
            with obs.trace_span("walk.round", round=7):
                obs.span_event("fault.fire", point="superstep")
        ev = [r for r in obs.recent() if r["kind"] == "event"][0]
        assert ev["fields"]["round"] == 7
        assert ev["fields"]["shard"] == 3          # from bare log_context
        assert ev["fields"]["point"] == "superstep"
        assert ev["span"] == "walk.round"

    def test_disabled_span_is_passthrough(self):
        with obs.override(enabled=False):
            with obs.trace_span("off", round=1) as f:
                assert f is None
                assert obs.current_span() is None
        assert obs.recent() == []

    def test_prefetch_thread_contextvar_isolation(self):
        """A span opened on the driver thread must be invisible to the
        prefetch thread (and vice versa) — the Prefetcher pattern in
        runtime.trainer runs fetches on a daemon thread."""
        from repro.data.pipeline import Prefetcher

        seen = []
        started = threading.Event()

        def fetch(step):
            with obs.trace_span("thread.fetch", step=step):
                seen.append(tuple(f["name"] for f in obs.span_stack()))
            started.set()
            return step

        with obs.trace_span("driver.loop", round=0):
            pf = Prefetcher(fetch, depth=1)
            try:
                pf.next()
                started.wait(5.0)
            finally:
                pf.close()
            # Driver-side stack untouched by the thread's spans.
            assert [f["name"] for f in obs.span_stack()] == ["driver.loop"]
        assert seen and all(names == ("thread.fetch",) for names in seen)

    def test_span_jsonl_stream(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with obs.override(jsonl_path=path):
            with obs.trace_span("walk.round", round=4):
                obs.span_event("tick")
        lines = [json.loads(s) for s in open(path).read().splitlines()]
        assert [r["kind"] for r in lines] == ["event", "span"]
        assert lines[1]["name"] == "walk.round"
        assert lines[1]["fields"]["round"] == 4


def host_events(tmp_path, body):
    """[(name, start_ns, dur_ns)] of the ``repro.*`` host events that a CPU
    ``jax.profiler`` trace records around ``body()``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    return [(e.name, e.start_ns, e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


class TestProfilerClock:
    def test_span_and_phase_on_profiler_trace(self, tmp_path):
        def body():
            with obs.trace_span("unit.span", round=1):
                with obs.phase("unit.phase"):
                    pass
        events = {n: (s, d) for n, s, d in host_events(tmp_path, body)}
        assert {"repro.unit.span", "repro.unit.phase"} <= set(events)
        s0, d0 = events["repro.unit.span"]
        s1, d1 = events["repro.unit.phase"]
        assert s0 <= s1 and s1 + d1 <= s0 + d0
        hist = obs.REGISTRY.snapshot()["histograms"]
        assert hist["span.unit.span.s"]["count"] == 1
        assert hist["span.unit.phase.s"]["count"] == 1

    def test_phase_is_light(self):
        """A phase keeps no frame, record or log context: only its
        histogram."""
        with obs.phase("unit.light") as ph:
            assert obs.current_span() is None
            assert obs.span_stack() == ()
        assert ph.wall_s is not None and ph.wall_s >= 0.0
        assert obs.recent() == []
        assert obs.REGISTRY.histogram("span.unit.light.s").count == 1

    def test_disabled_records_nothing_and_removes_hook(self, tmp_path):
        def body():
            with obs.trace_span("unit.off"):
                with obs.phase("unit.off_phase") as ph:
                    assert ph.wall_s is None
            gc.collect()
        with obs.override(enabled=False):
            assert obs_trace._COLLECTOR_HOOK not in gc.callbacks
            before = list(obs.REGISTRY.gc.collections)
            events = host_events(tmp_path, body)
            assert obs.REGISTRY.gc.collections == before
        assert events == []
        assert obs.REGISTRY.snapshot()["histograms"] == {}
        assert gc.callbacks.count(obs_trace._COLLECTOR_HOOK) == 1

    def test_gc_collection_is_a_phase_and_counted(self, tmp_path):
        names = [n for n, _, _ in host_events(tmp_path, gc.collect)]
        assert "repro.gc.collect.gen2" in names
        counters = obs.REGISTRY.snapshot()["counters"]
        assert counters["gc.collections.gen2"] >= 1
        assert counters["gc.pause_s.gen2"] > 0.0
        obs.reset()
        assert "gc.collections.gen2" not in \
            obs.REGISTRY.snapshot()["counters"]

    def test_gc_hook_takes_no_lock(self):
        """A collection can start while its own thread holds a telemetry
        lock; the hook must not wait on any of them."""
        done = threading.Event()
        locks = (obs.REGISTRY._lock, obs_recorder._LOCK, obs_config._LOCK)
        for lock in locks:
            lock.acquire()
        try:
            t = threading.Thread(target=lambda: (gc.collect(), done.set()),
                                 daemon=True)
            t.start()
            t.join(timeout=30.0)
            assert done.is_set() and not t.is_alive()
        finally:
            for lock in locks:
                lock.release()
        assert obs.REGISTRY.snapshot()["counters"]["gc.collections.gen2"] \
            >= 1


# --- logging satellite ------------------------------------------------------


class TestLoggingConfig:
    def test_handler_install_is_idempotent(self):
        root = logging.getLogger("repro")
        get_logger()
        n = len(root.handlers)
        for _ in range(5):
            get_logger("repro.sub")
        assert len(root.handlers) == n

    def test_level_reread_from_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
        assert refresh_log_level() == logging.DEBUG
        assert logging.getLogger("repro").level == logging.DEBUG
        monkeypatch.setenv("REPRO_LOG_LEVEL", "WARNING")
        get_logger()                  # get_logger also re-reads the env
        assert logging.getLogger("repro").level == logging.WARNING
        monkeypatch.delenv("REPRO_LOG_LEVEL")
        refresh_log_level()

    def test_span_close_logs_through_shared_formatter(self, monkeypatch):
        monkeypatch.setenv("REPRO_LOG_LEVEL", "DEBUG")
        refresh_log_level()
        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        h = Capture(level=logging.DEBUG)
        root = logging.getLogger("repro")
        root.addHandler(h)
        try:
            with obs.trace_span("walk.round", round=9):
                pass
        finally:
            root.removeHandler(h)
            monkeypatch.delenv("REPRO_LOG_LEVEL")
            refresh_log_level()
        close = [r for r in records if "span walk.round" in r.getMessage()]
        assert close, "span close line missing"


# --- flight recorder --------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        obs_recorder.resize(16)
        try:
            for i in range(100):
                obs.span_event("e", i=i)
            recs = obs.recent()
            assert len(recs) == 16
            assert recs[-1]["fields"]["i"] == 99
        finally:
            obs_recorder.resize(obs_recorder.DEFAULT_RING)

    def test_no_dump_without_flight_dir(self):
        assert obs.dump_flight_record("nope") is None

    def test_dump_on_wal_append_fault(self, graph, tmp_path):
        """Chaos-injected WAL crash → on-disk postmortem whose context
        carries the injection point and WAL seq of the dying submit."""
        flight = tmp_path / "flight"
        policy, spec, rounds, dsgl = _plan()
        p = StreamingEmbedPipeline(graph, policy, spec, rounds, dsgl)
        p.run()
        faults = FaultInjector(plan={"wal_append": [0]})
        driver = IngestDriver(str(tmp_path / "ing"), p,
                              cfg=IngestConfig(apply_every=100),
                              faults=faults)
        batch = EdgeBatch(insert=np.array([[1, 2], [3, 4]]))
        with obs.override(flight_dir=str(flight)):
            with pytest.raises(SimulatedFailure):
                driver.submit(batch)
        dumps = sorted(flight.glob("flight_fault_wal_append_*.json"))
        assert len(dumps) == 1
        doc = obs.load_flight_record(str(dumps[0]))
        assert doc["schema"] == "repro.flight_record.v1"
        assert doc["context"]["point"] == "wal_append"
        assert doc["context"]["seq"] == 1           # ingest.submit span field
        assert any(s["name"] == "ingest.submit" for s in doc["open_spans"])
        # The ring holds the durable append that preceded the crash.
        assert any(r["name"] == "ingest.wal_append" for r in doc["ring"])

    def test_dump_on_refresh_splice_fault(self, graph, tmp_path):
        """The acceptance scenario: a refresh_splice crash dumps a record
        whose faulting span carries round + graph_version (+ shard from
        the ambient log_context)."""
        flight = tmp_path / "flight"
        p = _pipeline(graph)
        p.run()
        faults = FaultInjector(plan={"refresh_splice": [0]})
        with obs.override(flight_dir=str(flight)):
            with pytest.raises(SimulatedFailure):
                p.recover_shard_loss(0, faults=faults)
        dumps = sorted(flight.glob("flight_fault_refresh_splice_*.json"))
        assert len(dumps) == 1
        doc = obs.load_flight_record(str(dumps[0]))
        ctx = doc["context"]
        assert ctx["point"] == "refresh_splice"
        assert "round" in ctx and "graph_version" in ctx and "shard" in ctx
        assert ctx["shard"] == 0
        spans = {s["name"]: s for s in doc["open_spans"]}
        assert "refresh.splice" in spans
        assert set(spans["refresh.splice"]["fields"]) >= {
            "round", "graph_version"}
        assert doc["metrics"]["counters"].get("faults.fired.refresh_splice"
                                              ) == 1

    def test_supervisor_restart_events(self):
        calls = []

        def attempt(i):
            calls.append(i)
            if i < 2:
                raise SimulatedFailure("boom")
            return "ok"

        out, restarts = run_with_restarts(attempt)
        assert out == "ok" and restarts == 2
        snap = obs.REGISTRY.snapshot()
        assert snap["counters"]["supervisor.restarts"] == 2
        events = [r for r in obs.recent()
                  if r["name"] == "supervisor.restart"]
        assert len(events) == 2


# --- RUN_TELEMETRY.json -----------------------------------------------------


class TestRunTelemetry:
    def test_round_trip(self, tmp_path):
        obs.inc("walk.supersteps", 17)
        obs.set_gauge("walk.pool_slots", 64)
        obs.observe("span.walk.round.s", 0.5)
        path = str(tmp_path / "RUN_TELEMETRY.json")
        doc = obs.write_run_telemetry(path, run={"bench": "unit",
                                                 "nodes": 128})
        loaded = obs.load_run_telemetry(path)
        assert loaded == json.loads(json.dumps(doc))
        assert loaded["schema"] == "repro.run_telemetry.v1"
        assert loaded["run"]["nodes"] == 128
        assert loaded["counters"]["walk.supersteps"] == 17
        assert loaded["histograms"]["span.walk.round.s"]["count"] == 1

    def test_schema_validation(self, tmp_path):
        path = str(tmp_path / "bad.json")
        with open(path, "w") as f:
            json.dump({"schema": "repro.run_telemetry.v1"}, f)
        with pytest.raises(ValueError, match="missing keys"):
            obs.load_run_telemetry(path)
        with open(path, "w") as f:
            json.dump({"schema": "nope", "run": {}, "counters": {},
                       "gauges": {}, "histograms": {}}, f)
        with pytest.raises(ValueError, match="unknown RUN_TELEMETRY"):
            obs.load_run_telemetry(path)


# --- ingest staleness on the shared reservoir -------------------------------


class TestIngestStaleness:
    def test_latency_histogram_exported(self, graph, tmp_path):
        p = _pipeline(graph)
        p.run()
        driver = IngestDriver(str(tmp_path / "ing"), p,
                              cfg=IngestConfig(apply_every=1))
        driver.submit(EdgeBatch(insert=np.array([[1, 2], [5, 9]])))
        s = driver.staleness()
        assert s["latency_p50_s"] is not None
        # Same reservoir feeds the registry export.
        snap = obs.REGISTRY.snapshot()
        assert snap["histograms"]["ingest.latency_s"]["count"] == 1
        assert snap["histograms"]["ingest.latency_s"]["p50"] == \
            pytest.approx(s["latency_p50_s"])
        assert snap["counters"]["ingest.drains"] >= 1

    def test_staleness_works_with_telemetry_off(self, graph, tmp_path):
        p = _pipeline(graph)
        p.run()
        with obs.override(enabled=False):
            driver = IngestDriver(str(tmp_path / "ing"), p,
                                  cfg=IngestConfig(apply_every=1))
            driver.submit(EdgeBatch(insert=np.array([[1, 2]])))
            s = driver.staleness()
        assert s["latency_p50_s"] is not None      # driver-owned, not gated


# --- the non-negotiable invariant: zero numerical footprint -----------------


def _run_plain(graph, enabled):
    with obs.override(enabled=enabled):
        p = _pipeline(graph)
        p.run()
        phi_in, phi_out = p.embeddings()
        return phi_in, phi_out, np.asarray(p.ring.walks).copy()


def _run_heal(graph, tmp_path, enabled, tag):
    """Divergence → rollback → replay with lr_backoff=1.0 (bit-neutral)."""
    with obs.override(enabled=enabled):
        faults = FaultInjector(inject_plan={"phi_nan": [3]})
        p = _pipeline(graph, health=HealthMonitor(
            HealthConfig(check_every=1, lr_backoff=1.0)))
        p.run(ckpt_root=str(tmp_path / f"heal_{tag}"),
              ckpt_every_rounds=1, faults=faults)
        assert p.health.rollbacks >= 1
        phi_in, phi_out = p.embeddings()
        return phi_in, phi_out, np.asarray(p.ring.walks).copy()


class TestBitIdentityOnVsOff:
    def test_plain_run(self, graph):
        on = _run_plain(graph, True)
        off = _run_plain(graph, False)
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)

    def test_across_heal(self, graph, tmp_path):
        on = _run_heal(graph, tmp_path, True, "on")
        off = _run_heal(graph, tmp_path, False, "off")
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)

    def test_serve_scores(self, tmp_path):
        """Telemetry on and off answer the same reads with the same bits."""
        from repro.ckpt.checkpoint import save_checkpoint
        from repro.runtime.serve import EmbedServer, ServeConfig

        phi = np.random.default_rng(5).standard_normal((64, 16)) \
            .astype(np.float32)
        save_checkpoint(str(tmp_path), 0, {"phi_in": phi},
                        meta={"graph_version": 0, "global_step": 0})
        rng = np.random.default_rng(6)
        queries = [{"u": int(rng.integers(0, 64)),
                    "candidates": rng.integers(0, 64, size=int(w))}
                   for w in rng.integers(1, 20, size=12)]
        queries += [{"u": int(u), "k": 5} for u in rng.integers(0, 64, 4)]
        answers = []
        for enabled in (True, False):
            with obs.override(enabled=enabled):
                srv = EmbedServer(ServeConfig(batch_slots=5))
                srv.offer_snapshot(str(tmp_path))
                answers.append(srv.serve(queries))
        for on, off in zip(*answers):
            np.testing.assert_array_equal(on.ids, off.ids)
            np.testing.assert_array_equal(on.scores, off.scores)
            assert on.wave == off.wave

    def test_across_resume(self, graph, tmp_path):
        """Telemetry ON for the interrupted+resumed run, OFF for the
        uninterrupted reference — the strongest cross-mode form."""
        policy, spec, rounds, dsgl = _plan()
        off_in, off_out, off_walks = _run_plain(graph, False)
        with obs.override(enabled=True):
            p = StreamingEmbedPipeline(graph, policy, spec, rounds, dsgl)
            root = str(tmp_path / "resume_ckpt")
            p.run(ckpt_root=root, ckpt_every_rounds=1)
            steps = sorted(int(d.split("_")[-1]) for d in os.listdir(root)
                           if d.startswith("step_")
                           and not d.endswith(".tmp"))
            q = StreamingEmbedPipeline.resume(root, policy, spec, dsgl,
                                              step=steps[0])
            q.run()
            phi_in, phi_out = q.embeddings()
            walks = np.asarray(q.ring.walks).copy()
        np.testing.assert_array_equal(phi_in, off_in)
        np.testing.assert_array_equal(phi_out, off_out)
        np.testing.assert_array_equal(walks, off_walks)
