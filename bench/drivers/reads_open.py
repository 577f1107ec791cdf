"""Open-loop pair-scoring reads at a fixed Poisson rate.

The schedule is drawn from the seed before the window: arrival times at
``rate_per_s``, a user drawn Zipf(s) and ``candidates`` ids drawn uniformly
for each read. One thread serves it: submit every read that is due, tick
one wave, repeat. After the window closes the queue is drained, for at
most ``drain_s``.

``pair_p95_ms`` is the 95th percentile, over every read due in the window,
of the time from when the read was due to when its wave returned. A read
the server shed, or that is still unanswered after the drain, misses every
limit: it counts as answered at the end of the drain, later than any read
that was answered, and as failed. How late the generator itself ran
(submit time minus due time) is printed on stderr. The check compares a
sample of the answers, drawn from the seed before the window, with the
reference scores, bit for bit.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, List

import numpy as np

import common
import serving


@dataclasses.dataclass
class State:
    server: Any
    phi: np.ndarray
    rng: np.random.Generator
    due: np.ndarray = None
    users: np.ndarray = None
    cands: np.ndarray = None
    checked: set = dataclasses.field(default_factory=set)
    answers: list = dataclasses.field(default_factory=list)


def schedule(rng: np.random.Generator, rate: float, seconds: float,
             users: serving.Users, num_nodes: int, candidates: int):
    """(due times, users, candidate ids) of every read due in the window."""
    gaps = rng.exponential(1.0 / rate, size=int(rate * seconds * 1.2) + 64)
    due = np.cumsum(gaps)
    while due[-1] < seconds:
        due = np.concatenate([due, due[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=len(gaps)))])
    due = due[due < seconds]
    return (due, users.draw(len(due)),
            rng.integers(0, num_nodes, (len(due), candidates),
                         dtype=np.int32))


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile by rank (the ceil(q/100 * m)-th smallest)."""
    v = np.sort(values)
    return float(v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)])


def setup(ctx) -> State:
    t = ctx.traffic
    n = int(ctx.config["graph"]["num_nodes"])
    phi = serving.make_table(ctx.seed, n, int(ctx.config["embed"]["dim"]))
    server = serving.start_server(phi, int(t["batch_slots"]))
    rng = np.random.default_rng(ctx.seed)
    st = State(server=server, phi=phi, rng=rng)
    users = serving.Users(rng, n, float(t["zipf"]))
    st.due, st.users, st.cands = schedule(
        rng, float(t["rate_per_s"]), ctx.seconds, users, n,
        int(t["candidates"]))
    # The reads whose answers the check compares, drawn before the window
    # so that the loop keeps only those answers.
    st.checked = set(rng.choice(len(st.due), replace=False, size=min(
        len(st.due), int(t["check_sample"]))).tolist())
    # Warm every wave size the loop can form, 1..batch_slots.
    for b in range(1, int(t["batch_slots"]) + 1):
        for i in range(b):
            server.submit(int(st.users[i]), st.cands[i])
        server.tick()
    return st


def window(st: State, ctx) -> common.WindowResult:
    t = ctx.traffic
    server = st.server
    m = len(st.due)
    done = np.full(m, np.inf)
    late = np.zeros(m)
    of_qid = {}
    shed = i = waves = served = 0
    t0 = time.perf_counter()

    def serve_wave():
        nonlocal waves, served
        with common.span("tick"):
            out = server.tick()
        now = time.perf_counter() - t0
        waves += 1
        served += len(out)
        for r in out:
            j = of_qid.pop(r.qid)
            done[j] = now
            if j in st.checked:
                st.answers.append((j, r.ids, r.scores))

    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        with common.span("submit"):
            while i < m and st.due[i] <= now:
                qid = server.submit(int(st.users[i]), st.cands[i])
                late[i] = time.perf_counter() - t0 - st.due[i]
                if qid is None:
                    shed += 1
                else:
                    of_qid[qid] = i
                i += 1
        if of_qid:
            serve_wave()
        elif i < m:
            with common.span("idle"):
                time.sleep(max(0.0, min(st.due[i] - now, 1e-3)))
    closed = time.perf_counter() - t0
    window_waves, window_served = waves, served
    backlog = len(of_qid) + (m - i)
    with common.span("drain"):
        while i < m:
            qid = server.submit(int(st.users[i]), st.cands[i])
            late[i] = time.perf_counter() - t0 - st.due[i]
            if qid is None:
                shed += 1
            else:
                of_qid[qid] = i
            i += 1
        while of_qid and time.perf_counter() - t0 < closed + float(
                t["drain_s"]):
            serve_wave()
    failed = int(np.sum(~np.isfinite(done)))
    # A read never answered counts as answered when the benchmark stops
    # waiting for it: later than any read that was answered.
    latency = np.where(np.isfinite(done), done,
                       closed + float(t["drain_s"])) - st.due
    p95 = percentile(latency, 95) * 1e3
    common.log(f"window reads={m} shed={shed} backlog_at_close={backlog} "
               f"unanswered={len(of_qid)} "
               f"waves={waves} closed={closed} p50_ms="
               f"{percentile(latency, 50) * 1e3} p95_ms={p95}")
    common.log(f"generator lateness ms p50={percentile(late, 50) * 1e3} "
               f"p99={percentile(late, 99) * 1e3} max={late.max() * 1e3}")
    return common.WindowResult(
        metrics={"pair_p95_ms": p95}, attempted=m, failed=failed,
        counts={"waves": window_waves, "reads": window_served,
                "batch_slots": int(t["batch_slots"]),
                "backlog_at_close": backlog, "p50_ms":
                percentile(latency, 50) * 1e3})


def release(st: State) -> None:
    st.server = None


def check(st: State, ctx, dtype=np.float32) -> List[common.Check]:
    answers = [(st.users[j], st.cands[j], ids, scores)
               for j, ids, scores in st.answers]
    found = serving.check_pairs(st.phi, answers, dtype)
    return serving.check_list(found, len(answers))
