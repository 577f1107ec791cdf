"""Closed-loop top-K reads: ``clients`` callers, each submits one top-K read
(k from the mix) for a user drawn Zipf(s), waits for its answer, and
submits again. One thread plays every client: submit what the clients have
outstanding, tick one wave, hand the answers back.

The window lasts ``--seconds``, and ends when the wave in flight at its
close is done. ``topk_per_s`` is the top-K answers completed over it.

The check compares a sample of the window's answers, drawn from the seed,
with the reference top-K over the same table, bit for bit. The sample is a
reservoir of ``check_sample`` answers (Algorithm R) filled as the answers
arrive, with one draw from the seed's generator per wave: every answer of
the window is equally likely to be in it, and the harness keeps no more
answers in a long window than in a short one.

At the close, standard error gets the waves' durations (p50, p99, max), the
waves longer than three times p50 (how many, their seconds and their
offsets into the window), and the collector's collections and pause
seconds by generation inside the window.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import time
from typing import Any, List

import numpy as np

import common
import serving
import work


class Reservoir:
    """A uniform sample of at most ``size`` items of a stream (Algorithm R):
    the first ``size`` are kept; the item numbered t (from 0, t >= size)
    takes slot j, for j drawn from [0, t], where j < size. One draw from
    ``rng`` per call of ``add`` past the first ``size`` items."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.kept: list = []
        self.seen = 0

    def add(self, items: list) -> None:
        fill = min(len(items), max(self.size - self.seen, 0))
        self.kept.extend(items[:fill])
        rest = items[fill:]
        if rest:
            numbers = self.seen + fill + np.arange(len(rest))
            for j, item in zip(self.rng.integers(0, numbers + 1), rest):
                if j < self.size:
                    self.kept[j] = item
        self.seen += len(items)


class GcPauses:
    """A ``gc.callbacks`` hook: collections and pause seconds by
    generation while it is installed."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.pause_s = [0.0, 0.0, 0.0]
        self._start = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            g = info["generation"]
            self.collections[g] += 1
            self.pause_s[g] += time.perf_counter() - self._start


def wave_summary(wave_s: np.ndarray, pauses: GcPauses) -> str:
    """One line on the window's waves and collections."""
    p50, p99 = np.percentile(wave_s, [50, 99])
    long = np.flatnonzero(wave_s > 3 * p50)
    starts = np.cumsum(wave_s) - wave_s
    def compact(values):
        return json.dumps(values, separators=(",", ":"))
    return (f"waves p50_s={p50} p99_s={p99} max_s={wave_s.max()} "
            f"long={len(long)} long_s={wave_s[long].sum()} "
            f"long_at_s={compact(np.round(starts[long], 4).tolist())} "
            f"gc_collections={compact(pauses.collections)} "
            f"gc_pause_s={compact(pauses.pause_s)}")


@dataclasses.dataclass
class State:
    server: Any
    phi: np.ndarray
    users: Any
    rng: np.random.Generator
    answers: Reservoir
    wave_s: np.ndarray = None


def setup(ctx) -> State:
    t = ctx.traffic
    n = int(ctx.config["graph"]["num_nodes"])
    phi = serving.make_table(ctx.seed, n, int(ctx.config["embed"]["dim"]))
    server = serving.start_server(phi, int(t["batch_slots"]))
    rng = np.random.default_rng(ctx.seed)
    st = State(server=server, phi=phi,
               users=serving.Users(rng, n, float(t["zipf"])), rng=rng,
               answers=Reservoir(int(t["check_sample"]), rng))
    # Warm the one wave shape the loop makes: all clients in one wave.
    for u in st.users.draw(int(t["clients"])):
        server.submit(int(u), k=int(t["k"]))
    server.tick()
    return st


def window(st: State, ctx) -> common.WindowResult:
    t = ctx.traffic
    k, clients = int(t["k"]), int(t["clients"])
    server = st.server
    users = {}
    waves = done = 0
    flops = bytes_ = 0.0
    n, d = st.phi.shape
    wave_s = np.empty(max(1024, int(ctx.seconds * 500)))
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        t0 = last = time.perf_counter()
        deadline = t0 + ctx.seconds
        outstanding = clients
        while True:
            with common.span("submit"):
                for u in st.users.draw(outstanding):
                    users[server.submit(int(u), k=k)] = int(u)
            with common.span("tick"):
                out = server.tick()
            done += len(out)
            f, b = work.topk_work(len(out), n, d)
            flops, bytes_ = flops + f, bytes_ + b
            st.answers.add([(users.pop(r.qid), r.ids, r.scores)
                            for r in out])
            outstanding = len(out)
            now = time.perf_counter()
            if waves == len(wave_s):
                wave_s = np.concatenate([wave_s, np.empty_like(wave_s)])
            wave_s[waves] = now - last
            last = now
            waves += 1
            if now >= deadline:
                break
    finally:
        gc.callbacks.remove(pauses)
    elapsed = time.perf_counter() - t0
    st.wave_s = wave_s[:waves]
    common.log(f"window waves={waves} answers={done} elapsed={elapsed}")
    common.log(wave_summary(st.wave_s, pauses))
    return common.WindowResult(
        metrics={"topk_per_s": done / elapsed},
        attempted=done + len(users), failed=0,
        counts={"waves": waves, "reads": done, "topk_flops": flops,
                "topk_bytes": bytes_})


def release(st: State) -> None:
    st.server = None


def check(st: State, ctx, dtype=np.float32) -> List[common.Check]:
    t = ctx.traffic
    picked = st.answers.kept
    t0 = time.perf_counter()
    found = serving.check_topk(st.phi, picked, int(t["k"]), dtype)
    common.log(f"checked {len(picked)} of {st.answers.seen} answers in "
               f"{time.perf_counter() - t0} s")
    return serving.check_list(found, len(picked))
