"""The read traffic: seeded schedules, latency from the due time, shed
reads as missing, and a closed loop that keeps its clients' reads out."""

import gc
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

import common
import serving
from drivers_import import load_driver


class FakeServer:
    """Answers every queued read in waves of ``slots``; sheds the reads
    whose index ``shed`` selects; sleeps ``tick_s`` per wave."""

    def __init__(self, slots=32, tick_s=0.0, shed=lambda i: False):
        self.slots, self.tick_s, self.shed = slots, tick_s, shed
        self.queue, self.seen, self.depths = [], 0, []

    def submit(self, u, candidates=None, *, k=None):
        i = self.seen
        self.seen += 1
        if self.shed(i):
            return None
        self.queue.append((i, u, candidates))
        return i

    def tick(self):
        self.depths.append(len(self.queue))
        wave, self.queue = self.queue[:self.slots], self.queue[self.slots:]
        time.sleep(self.tick_s)
        return [SimpleNamespace(qid=i, ids=np.zeros(1), scores=np.zeros(1))
                for i, _, _ in wave]


def open_state(server, seed=7, rate=2000.0, seconds=0.3):
    drv = load_driver("reads_open")
    rng = np.random.default_rng(seed)
    users = serving.Users(rng, 1000, 0.99)
    st = drv.State(server=server, phi=np.zeros((1000, 4), np.float32),
                   rng=rng)
    st.due, st.users, st.cands = drv.schedule(rng, rate, seconds, users,
                                              1000, 100)
    ctx = SimpleNamespace(seconds=seconds, trace=False, traffic={
        "batch_slots": server.slots, "drain_s": 5})
    return drv, st, ctx


def test_schedule_and_users_repeat_from_the_seed():
    drv = load_driver("reads_open")

    def draw(seed):
        rng = np.random.default_rng(seed)
        users = serving.Users(rng, 5000, 0.99)
        return drv.schedule(rng, 1000.0, 10.0, users, 5000, 100)

    a, b, c = draw(2**31 + 5), draw(2**31 + 5), draw(11)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0][:50], c[0][:50])
    due = a[0]
    assert due.max() < 10.0 and len(due) == pytest.approx(10000, rel=0.05)
    # Zipf(0.99): the most popular user takes about 1/H share of draws.
    counts = np.bincount(a[1], minlength=5000)
    h = np.sum(1.0 / np.arange(1, 5001) ** 0.99)
    assert counts.max() / len(a[1]) == pytest.approx(1 / h, rel=0.15)


def test_latency_counts_from_the_due_time():
    # Every wave takes 20 ms, so reads queue behind it: a read's latency
    # must hold its wait in the queue, not only its own wave.
    drv, st, ctx = open_state(FakeServer(slots=4, tick_s=0.02))
    out = drv.window(st, ctx)
    assert out.failed == 0
    assert out.metrics["pair_p95_ms"] > 5 * 20.0


def test_shed_reads_are_missing():
    drv, st, ctx = open_state(FakeServer(shed=lambda i: i % 10 == 0))
    out = drv.window(st, ctx)
    assert out.failed == len(st.due[::10]) > 0
    # Unanswered reads count as answered when the 5 s drain gives up.
    assert out.metrics["pair_p95_ms"] > 4000.0
    drv, st, ctx = open_state(FakeServer(shed=lambda i: i % 50 == 0))
    out = drv.window(st, ctx)
    assert out.failed > 0 and np.isfinite(out.metrics["pair_p95_ms"])


def test_closed_loop_keeps_every_client_outstanding():
    drv = load_driver("reads_closed")
    server = FakeServer(slots=8)
    rng = np.random.default_rng(3)
    st = drv.State(server=server, phi=np.zeros((100, 4), np.float32),
                   users=serving.Users(rng, 100, 0.99), rng=rng,
                   answers=drv.Reservoir(16, rng))
    ctx = SimpleNamespace(seconds=0.2, trace=False,
                          traffic={"k": 10, "clients": 8})
    out = drv.window(st, ctx)
    assert server.depths and set(server.depths) == {8}
    assert out.counts["reads"] == 8 * out.counts["waves"]


def stream_sample(seed, items, size=16, wave=8):
    drv = load_driver("reads_closed")
    res = drv.Reservoir(size, np.random.default_rng(seed))
    for i in range(0, items, wave):
        res.add(list(range(i, min(i + wave, items))))
    return res


@pytest.mark.parametrize("items", [5, 16, 17, 3000])
def test_check_sample_repeats_from_the_seed(items):
    a, b = stream_sample(2**31 + 9, items), stream_sample(2**31 + 9, items)
    assert a.kept == b.kept and a.seen == b.seen == items
    assert len(a.kept) == len(set(a.kept)) == min(16, items)
    if items > 16:
        assert stream_sample(10, items).kept != a.kept


def test_check_sample_is_drawn_from_the_whole_window():
    # 3,000 answers in waves of 8: each seed's sample reaches into the
    # first and the last third, and over many seeds each third holds
    # about a third of the picks.
    thirds = np.zeros(3)
    for seed in range(200):
        kept = np.asarray(stream_sample(seed, 3000).kept)
        third = np.bincount(kept // 1000, minlength=3)
        if seed < 20:
            assert third[0] > 0 and third[2] > 0, kept
        thirds += third
    assert np.allclose(thirds / thirds.sum(), 1 / 3, atol=0.03), thirds


class CollectingServer(FakeServer):
    """A FakeServer whose tick number ``stall`` collects the youngest
    generation and then stalls for 50 ms."""

    def __init__(self, stall, **kw):
        super().__init__(**kw)
        self.stall = stall

    def tick(self):
        if len(self.depths) == self.stall:
            gc.collect(0)
            time.sleep(0.05)
        return super().tick()


def test_closed_loop_logs_its_waves_and_collections(capsys):
    drv = load_driver("reads_closed")
    rng = np.random.default_rng(5)
    server = CollectingServer(stall=5, slots=8, tick_s=0.002)
    st = drv.State(server=server, phi=np.zeros((100, 4), np.float32),
                   users=serving.Users(rng, 100, 0.99), rng=rng,
                   answers=drv.Reservoir(16, rng))
    ctx = SimpleNamespace(seconds=0.5, trace=False,
                          traffic={"k": 10, "clients": 8})
    out = drv.window(st, ctx)
    assert len(st.wave_s) == out.counts["waves"] > 5
    assert not any(isinstance(c, drv.GcPauses) for c in gc.callbacks)
    line = [x for x in capsys.readouterr().err.splitlines()
            if "waves p50_s=" in x][-1]
    fields = dict(f.split("=", 1) for f in line.split("bench: ")[1].split(
        " ") if "=" in f)
    assert int(fields["long"]) >= 1 and float(fields["long_s"]) >= 0.05
    # The stalled wave is the sixth: it starts after five waves.
    starts = np.cumsum(st.wave_s) - st.wave_s
    assert starts[5] in [pytest.approx(x, abs=1e-3) for x in json.loads(
        fields["long_at_s"])]
    assert json.loads(fields["gc_collections"])[0] >= 1


def test_wave_summary_names_the_long_waves():
    drv = load_driver("reads_closed")
    pauses = drv.GcPauses()
    pauses("start", {"generation": 2})
    pauses("stop", {"generation": 2})
    waves = np.array([0.01] * 10 + [0.1] + [0.01] * 5)
    line = drv.wave_summary(waves, pauses)
    assert "long=1 " in line and "long_s=0.1 " in line
    assert "long_at_s=[0.1]" in line and "gc_collections=[0,0,1]" in line


def test_percentile_is_by_rank():
    drv = load_driver("reads_open")
    v = np.arange(1, 101, dtype=float)
    assert drv.percentile(v, 95) == 95.0
    v[-6:] = np.inf
    assert drv.percentile(v, 95) == float("inf")
    assert common.Check("x", float("nan"), 1.0).ok is False
