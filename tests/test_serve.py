"""Fault-tolerant embedding serving tests (DESIGN.md §14).

Four contracts under test:

* **bit-identity** — device scores (pair and top-K) match the NumPy
  oracle bit-for-bit for every dim / candidate width / batch shape the
  wave scheduler can produce (the FMA-contraction regression guard);
* **swap atomicity** — under concurrent submit/tick/swap, every
  response's scores match exactly ONE version's oracle (the version it
  is stamped with) — a half-swapped read is unrepresentable;
* **degraded reads** — torn / unhealthy candidates leave the active
  version serving (stamped stale), the ladder returns to fresh on the
  next good swap, and terminal states (nothing servable at all) dump a
  flight record and raise;
* **admission control** — deadline sheds use the wave-wall EMA
  predictor, overflow (real or drilled) sheds at the door, and a wave
  fault re-queues: an admitted query is never dropped.
"""

import threading

import numpy as np
import pytest

from repro import obs
from repro.ckpt.checkpoint import save_checkpoint
from repro.runtime.faults import FaultInjector, SimulatedFailure
from repro.runtime.health import SnapshotGate, SnapshotGateConfig
from repro.runtime.serve import (EmbedServer, ServeConfig, ServeError,
                                 oracle_scores, oracle_topk, wave_batches)

jnp = pytest.importorskip("jax.numpy")


def _phi(n=64, d=16, seed=0):
    return np.random.default_rng(seed).standard_normal((n, d)) \
        .astype(np.float32)


def _ckpt(root, step, phi, **meta):
    meta.setdefault("graph_version", 0)
    meta.setdefault("global_step", step)
    return save_checkpoint(str(root), step, {"phi_in": phi}, meta=meta)


def _server(**kw):
    kw.setdefault("cfg", ServeConfig(batch_slots=8))
    return EmbedServer(**kw)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Oracle bit-identity
# ---------------------------------------------------------------------------


class TestBitIdentity:
    @pytest.mark.parametrize("d", [8, 16, 17, 33, 64])
    def test_pair_scores_match_oracle_exactly(self, tmp_path, d):
        phi = _phi(d=d, seed=d)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        assert srv.offer_snapshot(str(tmp_path))
        rng = np.random.default_rng(d)
        for width in (1, 2, 5, 8, 16):
            cand = rng.integers(0, 64, size=width)
            qid = srv.submit(int(rng.integers(0, 64)), candidates=cand)
            srv.drain()
            r = srv.responses[qid]
            want = oracle_scores(phi, r.u, cand)
            assert np.array_equal(r.scores, want), (d, width)
            assert np.array_equal(r.ids, cand)
        assert srv._active._phi_t is None      # pair reads build no copy

    @pytest.mark.parametrize("k", [1, 5, 16])
    def test_topk_matches_oracle_exactly(self, tmp_path, k):
        phi = _phi(seed=k)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        qids = [srv.submit(u, k=k) for u in (0, 7, 63)]
        srv.drain()
        for qid, u in zip(qids, (0, 7, 63)):
            r = srv.responses[qid]
            vals, ids = oracle_topk(phi, u, k)
            assert np.array_equal(r.scores, vals)
            assert np.array_equal(r.ids, ids)
            assert u not in r.ids          # self excluded

    @pytest.mark.parametrize("n,d,wave,swap", [
        *[(n, d, wave, False) for n in (1000, 4099) for d in (16, 128)
          for wave in (1, 3, 8)],
        (1000, 16, 3, True)])
    def test_topk_waves_match_oracle_exactly(self, tmp_path, n, d, wave,
                                             swap):
        """Top-K waves over |V| off the lane and sublane widths, scored on
        the snapshot's d-major copy of phi, against the oracle bit for bit.
        With ``swap``, a second version goes live between two waves: its
        answers come from its own copy, and each version builds one."""
        phis = [_phi(n, d, seed=n + d + v) for v in range(1 + swap)]
        rng = np.random.default_rng(wave)
        builds = obs.REGISTRY.counter("serve.topk_phi_t_builds")
        with obs.override(enabled=True):
            before = builds.value
            srv = _server()
            for version, phi in enumerate(phis):
                _ckpt(tmp_path, version, phi)
                assert srv.offer_snapshot(str(tmp_path))
                users = rng.choice(n, size=wave, replace=False)
                qids = [srv.submit(int(u), k=10) for u in users]
                assert len(srv.tick()) == wave
                for qid, u in zip(qids, users):
                    r = srv.responses[qid]
                    vals, ids = oracle_topk(phi, u, 10)
                    assert r.served_version == version
                    assert np.array_equal(r.scores, vals), (version, u)
                    assert np.array_equal(r.ids, ids), (version, u)
                phi_t = np.asarray(srv._active.phi_t())
                assert phi_t.shape == (d, -(-n // 128) * 128)
                assert np.array_equal(phi_t[:, :n], phi.T)
                assert not phi_t[:, n:].any()
            assert builds.value - before == len(phis)

    def test_mixed_wave_groups_do_not_leak_padding(self, tmp_path):
        """One wave mixing top-K and several candidate widths: each
        response is trimmed to its own query's shape and exact."""
        phi = _phi(seed=42)
        _ckpt(tmp_path, 0, phi)
        srv = _server(cfg=ServeConfig(batch_slots=32))
        assert srv.offer_snapshot(str(tmp_path))
        specs = [{"u": 1, "candidates": [2, 3, 4]},
                 {"u": 5, "k": 4},
                 {"u": 9, "candidates": [10]},
                 {"u": 11, "candidates": list(range(20))},
                 {"u": 13, "k": 4}]
        out = srv.serve(specs)
        assert all(r is not None for r in out)
        for spec, r in zip(specs, out):
            if "candidates" in spec:
                assert len(r.scores) == len(spec["candidates"])
                assert np.array_equal(
                    r.scores, oracle_scores(phi, spec["u"],
                                            spec["candidates"]))
            else:
                vals, ids = oracle_topk(phi, spec["u"], spec["k"])
                assert np.array_equal(r.scores, vals)
                assert np.array_equal(r.ids, ids)

    def test_wave_batches_shapes(self):
        assert [len(w) for w in wave_batches(list(range(10)), 4)] \
            == [4, 4, 2]
        assert list(wave_batches([], 4)) == []


# ---------------------------------------------------------------------------
# Versioned snapshot swap
# ---------------------------------------------------------------------------


class TestSnapshotSwap:
    def test_swap_is_monotone_and_stamped(self, tmp_path):
        a, b = _phi(seed=1), _phi(seed=2)
        _ckpt(tmp_path, 0, a)
        srv = _server()
        assert srv.offer_snapshot(str(tmp_path))
        q0 = srv.submit(3, candidates=[1, 2])
        srv.drain()
        _ckpt(tmp_path, 1, b)
        assert srv.offer_snapshot(str(tmp_path))
        q1 = srv.submit(3, candidates=[1, 2])
        srv.drain()
        assert srv.responses[q0].served_version == 0
        assert srv.responses[q1].served_version == 1
        assert np.array_equal(srv.responses[q0].scores,
                              oracle_scores(a, 3, [1, 2]))
        assert np.array_equal(srv.responses[q1].scores,
                              oracle_scores(b, 3, [1, 2]))
        assert srv.swaps == 2

    def test_reoffer_of_active_version_is_noop(self, tmp_path):
        _ckpt(tmp_path, 0, _phi())
        srv = _server()
        assert srv.offer_snapshot(str(tmp_path))
        assert not srv.offer_snapshot(str(tmp_path))
        assert srv.swaps == 1
        assert srv.stats()["freshness"] == "fresh"

    def test_torn_candidate_falls_back_and_keeps_serving(self, tmp_path):
        """A torn (manifest-less) newer step is invisible: the loader
        falls back to the active version, which keeps serving fresh."""
        phi = _phi(seed=3)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        torn = tmp_path / "step_00000001"
        torn.mkdir()
        (torn / "phi_in.npy").write_bytes(b"\x93NUMPY garbage")
        assert not srv.offer_snapshot(str(tmp_path))
        assert srv.active_version() == 0
        r = srv.serve([{"u": 2, "candidates": [4, 5]}])[0]
        assert np.array_equal(r.scores, oracle_scores(phi, 2, [4, 5]))
        assert srv.stats()["availability"] == 1.0

    def test_no_snapshot_at_all_is_terminal(self, tmp_path):
        srv = _server()
        with pytest.raises(ServeError):
            srv.offer_snapshot(str(tmp_path / "empty"))

    def test_swap_window_fault_leaves_old_version_serving(self, tmp_path):
        """Drill point "swap" fires inside the swap window, before the
        commit: the offer dies but the previous version keeps serving."""
        a, b = _phi(seed=4), _phi(seed=5)
        _ckpt(tmp_path, 0, a)
        faults = FaultInjector(plan={"swap": (1,)})
        srv = _server(faults=faults)
        assert srv.offer_snapshot(str(tmp_path))          # occurrence 0
        _ckpt(tmp_path, 1, b)
        with pytest.raises(SimulatedFailure):
            srv.offer_snapshot(str(tmp_path))             # occurrence 1
        assert srv.active_version() == 0
        r = srv.serve([{"u": 6, "candidates": [7]}])[0]
        assert np.array_equal(r.scores, oracle_scores(a, 6, [7]))
        assert r.served_version == 0
        # Retry after the (transient) fault: the swap completes.
        assert srv.offer_snapshot(str(tmp_path))
        assert srv.active_version() == 1

    def test_concurrent_swap_atomicity(self, tmp_path):
        """Queries racing ~30 swaps: every response's scores must match
        the oracle of EXACTLY the version it is stamped with — the
        captured-snapshot invariant at the bit level."""
        phis = {v: _phi(seed=100 + v) for v in range(30)}
        _ckpt(tmp_path, 0, phis[0])
        srv = _server(cfg=ServeConfig(batch_slots=4))
        srv.offer_snapshot(str(tmp_path))
        stop = threading.Event()
        errors: list = []

        def swapper():
            try:
                for v in range(1, 30):
                    _ckpt(tmp_path, v, phis[v])
                    assert srv.offer_snapshot(str(tmp_path))
            except Exception as e:               # pragma: no cover
                errors.append(e)
            finally:
                stop.set()

        t = threading.Thread(target=swapper)
        t.start()
        cand = np.array([1, 2, 3, 4, 5])
        qids = []
        while not stop.is_set() or srv.stats()["queue_depth"]:
            qid = srv.submit(9, candidates=cand)
            if qid is not None:
                qids.append(qid)
            srv.tick()
        t.join()
        srv.drain()
        assert not errors
        assert srv.swaps == 30 and len(qids) > 0
        for qid in qids:
            r = srv.responses[qid]
            want = oracle_scores(phis[r.served_version], 9, cand)
            assert np.array_equal(r.scores, want), qid


# ---------------------------------------------------------------------------
# Health-gated swap
# ---------------------------------------------------------------------------


class TestHealthGate:
    def test_nonfinite_candidate_rejected_serves_stale(self, tmp_path):
        phi = _phi(seed=6)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        bad = phi.copy()
        bad[5, 0] = np.nan
        _ckpt(tmp_path, 1, bad)
        assert not srv.offer_snapshot(str(tmp_path))
        assert srv.rejected_candidates == 1
        assert srv.active_version() == 0
        r = srv.serve([{"u": 1, "candidates": [2]}])[0]
        assert r.freshness == "stale"       # a newer version exists but
        assert r.served_version == 0        # is unhealthy
        assert np.array_equal(r.scores, oracle_scores(phi, 1, [2]))

    def test_good_swap_clears_stale_flag(self, tmp_path):
        phi = _phi(seed=7)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        bad = np.full_like(phi, np.inf)
        _ckpt(tmp_path, 1, bad)
        assert not srv.offer_snapshot(str(tmp_path))
        assert srv.stats()["freshness"] == "stale"
        _ckpt(tmp_path, 2, _phi(seed=8))
        assert srv.offer_snapshot(str(tmp_path))
        assert srv.stats()["freshness"] == "fresh"

    def test_version_regression_rejected_by_gate(self):
        gate = SnapshotGate(SnapshotGateConfig())
        phi = _phi()
        ok, _ = gate.admit(phi, version=5)
        assert ok
        ok, reason = gate.admit(phi, version=5)
        assert not ok and reason == "version_regression"
        ok, reason = gate.admit(phi, version=6, graph_version=-1)
        assert not ok and reason == "graph_version_regression"

    def test_norm_spike_rejected_after_warmup(self):
        gate = SnapshotGate(SnapshotGateConfig(spike_factor=4.0,
                                               warmup_admits=1))
        phi = _phi(seed=9)
        assert gate.admit(phi, version=0)[0]
        ok, reason = gate.admit(phi * 100.0, version=1)
        assert not ok and reason == "norm_spike"
        assert gate.admit(phi * 1.01, version=2)[0]

    def test_rejected_first_candidate_is_terminal(self, tmp_path):
        bad = np.full((8, 4), np.nan, np.float32)
        _ckpt(tmp_path, 0, bad)
        srv = _server()
        with pytest.raises(ServeError, match="rejected"):
            srv.offer_snapshot(str(tmp_path))


# ---------------------------------------------------------------------------
# Degrade ladder + admission control
# ---------------------------------------------------------------------------


class TestDegradeLadderAndAdmission:
    def test_refresh_state_moves_the_ladder(self, tmp_path):
        phi = _phi(seed=10)
        _ckpt(tmp_path, 0, phi)
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        srv.note_refresh("degraded")
        r = srv.serve([{"u": 1, "candidates": [2]}])[0]
        assert r.freshness == "stale"
        srv.note_refresh("ok")
        r = srv.serve([{"u": 1, "candidates": [2]}])[0]
        assert r.freshness == "fresh"
        with pytest.raises(AssertionError):
            srv.note_refresh("on_fire")

    def test_no_version_sheds_at_admission(self):
        srv = _server()
        assert srv.submit(1, candidates=[2]) is None
        assert srv.shed == {"no_version": 1}

    def test_queue_overflow_sheds(self, tmp_path):
        _ckpt(tmp_path, 0, _phi())
        srv = _server(cfg=ServeConfig(batch_slots=4, max_queue=3))
        srv.offer_snapshot(str(tmp_path))
        qids = [srv.submit(1, candidates=[2]) for _ in range(5)]
        assert sum(q is not None for q in qids) == 3
        assert srv.shed["overflow"] == 2
        srv.drain()
        assert srv.stats()["availability"] == 1.0   # of admitted

    def test_queue_overflow_drill(self, tmp_path):
        _ckpt(tmp_path, 0, _phi())
        faults = FaultInjector(inject_plan={"queue_overflow": (1,)})
        srv = _server(faults=faults)
        srv.offer_snapshot(str(tmp_path))
        assert srv.submit(1, candidates=[2]) is not None
        assert srv.submit(1, candidates=[2]) is None   # drilled occurrence
        assert srv.submit(1, candidates=[2]) is not None
        assert srv.shed["overflow"] == 1

    def test_deadline_shed_uses_wave_ema_prediction(self, tmp_path):
        """After a slow wave (fake clock), a tight deadline is shed at
        admission while a loose one is admitted."""
        clock = FakeClock()
        _ckpt(tmp_path, 0, _phi(seed=11))
        srv = _server(cfg=ServeConfig(batch_slots=4, headroom=1.0),
                      clock=clock)
        srv.offer_snapshot(str(tmp_path))
        # First wave is never shed (no EMA yet); the fake clock charges
        # it 1s of wall, seeding the predictor.
        assert srv.submit(1, candidates=[2],
                          deadline_s=0.1) is not None
        inner = srv._score_wave

        def slow(wave, snap):
            clock.advance(1.0)
            return inner(wave, snap)

        srv._score_wave = slow
        srv.drain()
        assert srv._wave_ema == pytest.approx(1.0)
        # predicted = 1 wave * 1s EMA * 1.0 headroom = 1s.
        assert srv.submit(2, candidates=[3], deadline_s=0.1) is None
        assert srv.shed["deadline"] == 1
        assert srv.submit(2, candidates=[3], deadline_s=10.0) is not None
        srv.drain()
        assert srv.stats()["availability"] == 1.0

    def test_wave_fault_requeues_admitted_queries(self, tmp_path):
        """The "serve_wave" drill kills a wave mid-flight: the wave goes
        back to the queue front and the retry answers every query."""
        phi = _phi(seed=12)
        _ckpt(tmp_path, 0, phi)
        faults = FaultInjector(plan={"serve_wave": (0,)})
        srv = _server(faults=faults)
        srv.offer_snapshot(str(tmp_path))
        qids = [srv.submit(u, candidates=[1, 2]) for u in (3, 4, 5)]
        with pytest.raises(SimulatedFailure):
            srv.tick()
        assert srv.wave_faults == 1
        assert srv.stats()["queue_depth"] == 3       # nothing dropped
        srv.drain()
        for qid, u in zip(qids, (3, 4, 5)):
            assert np.array_equal(srv.responses[qid].scores,
                                  oracle_scores(phi, u, [1, 2]))
        assert srv.stats()["availability"] == 1.0

    def test_stats_shape(self, tmp_path):
        _ckpt(tmp_path, 0, _phi())
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        srv.serve([{"u": 1, "candidates": [2]}, {"u": 3, "k": 2}])
        s = srv.stats()
        assert s["served"] == 2 and s["availability"] == 1.0
        assert s["served_by_version"] == {0: 2}
        assert s["served_by_freshness"]["fresh"] == 2
        assert s["latency_p50_s"] >= 0.0
        assert s["offered_total"] == s["admitted"] + s["shed_total"]


# ---------------------------------------------------------------------------
# The wave's phases and the flight-recorder ring
# ---------------------------------------------------------------------------


class TestWavePhases:
    CHILDREN = ("serve.form", "serve.group", "serve.dispatch",
                "serve.fetch", "serve.respond")

    def test_children_nest_in_tick_and_responses_carry_wave(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        phi = _phi(seed=21)
        _ckpt(tmp_path / "snap", 0, phi)
        srv = _server(cfg=ServeConfig(batch_slots=4))
        srv.offer_snapshot(str(tmp_path / "snap"))
        qids = [srv.submit(u, candidates=[1, 2, 3]) for u in range(6)]
        qids.append(srv.submit(7, k=3))
        log_dir = tmp_path / "trace"
        jax.profiler.start_trace(str(log_dir))
        try:
            srv.drain()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        events = [(e.name[len("repro."):], e.start_ns,
                   e.start_ns + e.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith("repro.serve.")]
        ticks = [(s, e) for n, s, e in events if n == "serve.tick"]
        assert len(ticks) == 2
        for lo, hi in ticks:
            inside = sorted((s, e, n) for n, s, e in events
                            if lo <= s and e <= hi and n != "serve.tick")
            assert [n for _, _, n in inside] == list(self.CHILDREN)
            for (_, end, _), (start, _, _) in zip(inside, inside[1:]):
                assert end <= start
        waves = [srv.responses[q].wave for q in qids]
        assert waves == [0, 0, 0, 0, 1, 1, 1]
        for q, u in zip(qids[:6], range(6)):
            assert np.array_equal(srv.responses[q].scores,
                                  oracle_scores(phi, u, [1, 2, 3]))

    def test_queue_wait_per_read(self, tmp_path):
        from repro import obs

        clock = FakeClock()
        _ckpt(tmp_path, 0, _phi())
        srv = _server(clock=clock)
        srv.offer_snapshot(str(tmp_path))
        obs.reset()
        srv.submit(1, candidates=[2])
        clock.advance(0.5)
        srv.submit(2, candidates=[3])
        clock.advance(0.25)
        srv.tick()
        waits = obs.REGISTRY.histogram("serve.queue_wait_s")
        assert waits.count == 2
        assert sorted(waits.values()) == pytest.approx([0.25, 0.75])

    def test_ring_keeps_offer_after_many_ticks(self, tmp_path):
        """Waves leave no record in the flight recorder, so a thousand of
        them do not push out the snapshot offer a postmortem needs."""
        from repro import obs

        obs.reset()
        _ckpt(tmp_path, 0, _phi())
        srv = _server()
        srv.offer_snapshot(str(tmp_path))
        for i in range(1000):
            srv.submit(i % 64, candidates=[1, 2])
            srv.tick()
        assert srv.served == 1000
        assert "serve.offer" in [r["name"] for r in obs.recent()]
