"""Closed-loop top-K reads: ``clients`` callers, each submits one top-K read
(k from the mix) for a user drawn Zipf(s), waits for its answer, and
submits again. One thread plays every client: submit what the clients have
outstanding, tick one wave, hand the answers back.

``topk_per_s`` is the top-K answers completed over the window, which ends
when the wave in flight at its close is done. The check compares a sample
of the window's answers, drawn from the seed, with the reference top-K
over the same table, bit for bit.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List

import numpy as np

import common
import serving
import work


@dataclasses.dataclass
class State:
    server: Any
    phi: np.ndarray
    users: Any
    rng: np.random.Generator
    answers: list = dataclasses.field(default_factory=list)


def setup(ctx) -> State:
    t = ctx.traffic
    n = int(ctx.config["graph"]["num_nodes"])
    phi = serving.make_table(ctx.seed, n, int(ctx.config["embed"]["dim"]))
    server = serving.start_server(phi, int(t["batch_slots"]))
    rng = np.random.default_rng(ctx.seed)
    st = State(server=server, phi=phi,
               users=serving.Users(rng, n, float(t["zipf"])), rng=rng)
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
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    outstanding = clients
    while True:
        with common.span("submit"):
            for u in st.users.draw(outstanding):
                users[server.submit(int(u), k=k)] = int(u)
        with common.span("tick"):
            out = server.tick()
        waves += 1
        done += len(out)
        f, b = work.topk_work(len(out), n, d)
        flops, bytes_ = flops + f, bytes_ + b
        for r in out:
            st.answers.append((users.pop(r.qid), r.ids, r.scores))
        outstanding = len(out)
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    common.log(f"window waves={waves} answers={done} elapsed={elapsed}")
    return common.WindowResult(
        metrics={"topk_per_s": done / elapsed},
        attempted=done + len(users), failed=0,
        counts={"waves": waves, "reads": done, "topk_flops": flops,
                "topk_bytes": bytes_})


def release(st: State) -> None:
    st.server = None


def check(st: State, ctx, dtype=np.float32) -> List[common.Check]:
    t = ctx.traffic
    picked = serving.sample(st.rng, st.answers, int(t["check_sample"]))
    t0 = time.perf_counter()
    found = serving.check_topk(st.phi, picked, int(t["k"]), dtype)
    common.log(f"checked {len(picked)} of {len(st.answers)} answers in "
               f"{time.perf_counter() - t0} s")
    return serving.check_list(found, len(picked))
