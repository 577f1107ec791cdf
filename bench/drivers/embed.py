"""The embed job's steady state, driven through the pipeline's own objects.

Set-up builds ``StreamingEmbedPipeline`` (phi, ring, walk and train keys,
learning-rate schedule) from the configuration and ``--seed``, walks round 0
into the ring, builds the alias table from the ring's counts, and drives
the first ``first_steps`` training chunks through the same call the window
makes; those chunks are what the DSGL reference follows. The window then
does what the pipeline's round does, interleaved: 50-lifetime
``train_chunk`` calls over the current round's ring slots, and the next
round's 4096-source ``run_walk_batch`` calls, held until the round ends,
one walk walked for each walk trained. At a round boundary the held walks
are appended, the counts pulled and the alias table rebuilt. At most
``in_flight`` chunks are queued ahead of the device. The window ends when
its last chunk and walk batch are ready.

``embed_walks_per_s`` is the walks trained (lifetimes x G x W) over the
window. The checks: every walk the window produced starts at its source,
has a length in [min_len, max_len] with -1 after it, and steps only along
arcs of the graph; the ring's counts equal a recount; and the first chunks'
losses and table changes match the reference's.

Public names of the program this driver calls: ``EmbedConfig``,
``make_walk_plan``, ``DSGLConfig``, ``build_alias_table``, ``train_chunk``,
``run_walk_batch``, ``ring_append_donated``, ``ring_chunk_indices``,
``CSRGraph``, ``StreamingEmbedPipeline`` (constructor; attributes
``phi_in``, ``phi_out``, ``ring``, ``key_walk``, ``key_train``,
``sources``, ``graph``, ``policy``, ``spec``, ``steps_per_round``,
``total_steps``, ``global_step``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, List

import numpy as np

import common
import graphs
import reference
import work


@dataclasses.dataclass
class State:
    pipe: Any
    arc_keys: np.ndarray
    table: Any
    round: int = 0
    round_lifetimes: int = 0           # lifetimes trained in this round
    next_cursor: int = 0               # next source of the next round
    held: list = dataclasses.field(default_factory=list)
    first: list = dataclasses.field(default_factory=list)
    readings: dict = dataclasses.field(default_factory=dict)
    recount: Any = None
    ocn_off: Any = None
    window_walks: list = dataclasses.field(default_factory=list)
    window_chunks: list = dataclasses.field(default_factory=list)
    meta: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)


def _recount_fn():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def recount(walks, n):
        ids = jnp.where(walks >= 0, walks, n).reshape(-1)
        return jnp.zeros(n + 1, jnp.int32).at[ids].add(1)[:n]
    return recount


def _norm_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: jnp.sqrt(jnp.sum((a - b) ** 2)))


def load_graph(ctx):
    import jax.numpy as jnp
    from repro.graph.csr import CSRGraph

    arrays = graphs.load(ctx.cell.config_name, ctx.config["graph"])
    graph = CSRGraph(indptr=jnp.asarray(arrays["indptr"], jnp.int32),
                     indices=jnp.asarray(arrays["indices"], jnp.int32),
                     edge_cm=jnp.asarray(arrays["edge_cm"], jnp.int32))
    common.log(f"graph cached={arrays['cached']} nodes={graph.num_nodes} "
               f"arcs={graph.num_edges}")
    return graph, arrays["arc_keys"]


def build_pipeline(ctx, graph):
    from repro.core.api import EmbedConfig, make_walk_plan
    from repro.core.dsgl import DSGLConfig
    from repro.runtime.trainer import StreamingEmbedPipeline

    e = ctx.config["embed"]
    cfg = EmbedConfig(
        method=e["method"], info_termination=e["info_termination"],
        max_len=e["max_len"], min_len=e["min_len"], mu=e["mu"],
        reg_start=e["reg_start"], delta=e["delta"], d_window=e["d_window"],
        dim=e["dim"], window=e["window"], negatives=e["negatives"],
        epochs=e["epochs"], lr=e["lr"], multi_windows=e["multi_windows"],
        rng_mode=e["rng_mode"])
    policy, spec, rounds = make_walk_plan(cfg)
    rounds = dict(rounds, max_rounds=e["max_rounds"])
    dsgl = DSGLConfig(
        dim=e["dim"], window=e["window"], negatives=e["negatives"],
        multi_windows=e["multi_windows"], batch_groups=e["batch_groups"],
        epochs=e["epochs"], lr=e["lr"], min_lr=e["min_lr"],
        neg_power=e["neg_power"], sync_period=e["sync_period"],
        seed=reference.jax_seed(ctx.seed), use_kernel=e["use_kernel"])
    return StreamingEmbedPipeline(graph, policy, spec, rounds, dsgl,
                                  num_shards=e["num_shards"],
                                  walker_batch=e["walker_batch"])


def lr_schedule(step: int, count: int, total: int, lr: float,
                min_lr: float) -> np.ndarray:
    """The pipeline's a-priori linear decay, one rate per lifetime."""
    fracs = (step + np.arange(count)) / max(total, 1)
    return np.maximum(lr * (1.0 - fracs), min_lr).astype(np.float32)


def chunk_keys(key_train, step: int, total: int):
    """(slot-sampling key, negative-sampling key) of the chunk at ``step``."""
    import jax
    return (jax.random.fold_in(key_train, step),
            jax.random.fold_in(key_train, 2 * total + step))


def walk_batch(st: State):
    """Dispatch the next round's next batch of sources."""
    import jax
    import jax.numpy as jnp
    from repro.core.walker import run_walk_batch

    pipe = st.pipe
    src = pipe.sources[st.next_cursor:st.next_cursor + pipe.walker_batch]
    key = jax.random.fold_in(pipe.key_walk, st.round + 1)
    out = run_walk_batch(pipe.graph, jnp.asarray(src, jnp.int32), key,
                         pipe.policy, pipe.spec)
    st.next_cursor += len(src)
    st.held.append((src, out))
    st.window_walks.append((src, out))
    return src, out


def train_step(st: State):
    """One chunk of ``sync_period`` lifetimes over the current round's
    slots, as the pipeline trains it; returns (walks, losses)."""
    import jax.numpy as jnp
    from repro.core.dsgl import train_chunk
    from repro.data.pipeline import ring_chunk_indices

    pipe, cfg = st.pipe, st.pipe.cfg
    n = len(pipe.sources)
    count = cfg.sync_period
    ck, ck2 = chunk_keys(pipe.key_train, pipe.global_step, pipe.total_steps)
    base = (st.round * n) % pipe.ring.capacity
    idx = ring_chunk_indices(ck, base, n, count, pipe.num_shards,
                             cfg.batch_groups, cfg.multi_windows)
    walks = pipe.ring.walks[idx]
    lrs = lr_schedule(pipe.global_step, count, pipe.total_steps,
                      cfg.lr, cfg.min_lr)
    pipe.phi_in, pipe.phi_out, losses = train_chunk(
        pipe.phi_in, pipe.phi_out, walks, st.table, jnp.zeros(0, jnp.int32),
        ck2, jnp.asarray(lrs), cfg.window, cfg.negatives, cfg.use_kernel,
        False)
    pipe.global_step += count
    st.round_lifetimes += count
    return walks, losses


def round_boundary(st: State):
    """Append the next round's walks, pull the counts, rebuild the table."""
    import jax.numpy as jnp
    from repro.core.corpus import ring_append_donated
    from repro.core.dsgl import build_alias_table

    pipe = st.pipe
    while st.next_cursor < len(pipe.sources):
        walk_batch(st)
    for _, out in st.held:
        pipe.ring = ring_append_donated(pipe.ring, out.path,
                                        out.info.L.astype(jnp.int32))
    st.held = []
    st.table = build_alias_table(np.asarray(pipe.ring.ocn),
                                 pipe.cfg.neg_power)
    st.round += 1
    st.round_lifetimes = 0
    st.next_cursor = 0


def setup(ctx) -> State:
    import jax
    import jax.numpy as jnp
    from repro.core.corpus import ring_append_donated
    from repro.core.dsgl import build_alias_table
    from repro.core.walker import run_walk_batch

    graph, arc_keys = load_graph(ctx)
    pipe = build_pipeline(ctx, graph)
    n = graph.num_nodes
    key0 = jax.random.fold_in(pipe.key_walk, 0)
    with common.span("round0"):
        for s in range(0, n, pipe.walker_batch):
            src = jnp.asarray(pipe.sources[s:s + pipe.walker_batch])
            out = run_walk_batch(pipe.graph, src, key0, pipe.policy,
                                 pipe.spec)
            pipe.ring = ring_append_donated(pipe.ring, out.path,
                                            out.info.L.astype(jnp.int32))
    recount = _recount_fn()(pipe.ring.walks, n)
    st = State(pipe=pipe, arc_keys=arc_keys, table=build_alias_table(
        np.asarray(pipe.ring.ocn), pipe.cfg.neg_power))
    st.recount = np.asarray(recount)
    st.ocn_off = int(np.sum(st.recount != np.asarray(pipe.ring.ocn)))

    # The first chunks go through the window's own call; their walks,
    # losses and table changes are what the reference follows.
    norm = _norm_fn()
    phi0 = (jnp.copy(pipe.phi_in), jnp.copy(pipe.phi_out))
    steps = int(ctx.traffic["first_steps"])
    for i in range(steps):
        walks, losses = train_step(st)
        st.first.append((walks, losses))
        if i in (0, steps - 1):
            st.readings[i] = (norm(pipe.phi_in, phi0[0]),
                              norm(pipe.phi_out, phi0[1]))
    del phi0
    jax.block_until_ready((pipe.phi_in, st.readings))
    common.log(f"round0 walks={n} first_steps={steps} "
               f"steps_per_round={pipe.steps_per_round}")
    return st


def window(st: State, ctx) -> common.WindowResult:
    import jax

    pipe = st.pipe
    cfg = pipe.cfg
    per_chunk = cfg.sync_period * cfg.batch_groups * cfg.multi_windows
    in_flight = int(ctx.traffic["in_flight"])
    pending: deque = deque()
    trained = walked = chunks = 0
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while time.perf_counter() < deadline:
        with common.span("train_chunk"):
            walks, losses = train_step(st)
        pending.append(losses)
        chunks += 1
        trained += per_chunk
        if ctx.trace:
            st.window_chunks.append(walks)
        while walked < trained and st.next_cursor < len(pipe.sources):
            with common.span("walk_batch"):
                src, _ = walk_batch(st)
            walked += len(src)
        if st.round_lifetimes >= pipe.steps_per_round:
            with common.span("round_boundary"):
                round_boundary(st)
        if len(pending) > in_flight:
            with common.span("wait"):
                pending.popleft().block_until_ready()
    with common.span("drain"):
        jax.block_until_ready((pipe.phi_in, pipe.phi_out, list(pending),
                               [o.path for _, o in st.window_walks[-1:]]))
    elapsed = time.perf_counter() - t0
    common.log(f"window chunks={chunks} walks_trained={trained} "
               f"walks_walked={walked} elapsed={elapsed}")
    st.counts = {"walks_trained": trained, "walks_walked": walked,
                 "chunks": chunks}
    return common.WindowResult(
        metrics={"embed_walks_per_s": trained / elapsed},
        attempted=trained, failed=0, counts=st.counts)


def release(st: State) -> None:
    """Count the SGNS work of the traced window's chunks (outside the
    window), pull what the checks need to the host and free the device
    state."""
    if st.window_chunks:
        e = st.pipe.cfg
        lengths = np.concatenate([
            work.walk_lengths(np.asarray(w)).reshape(-1, e.multi_windows)
            for w in st.window_chunks])
        st.counts["sgns_flops"], st.counts["sgns_bytes"] = work.sgns_work(
            lengths, e.window, e.negatives, e.dim)
        st.window_chunks = []
    paths, lengths, sources = [], [], []
    for src, out in st.window_walks:
        paths.append(np.asarray(out.path))
        lengths.append(np.asarray(out.info.L))
        sources.append(np.asarray(src))
    st.window_walks = (paths, lengths, sources)
    st.first = [(np.asarray(w), float(np.sum(np.asarray(loss))))
                for w, loss in st.first]
    st.readings = {i: tuple(float(x) for x in v)
                   for i, v in st.readings.items()}
    pipe = st.pipe
    st.meta = {"n": len(pipe.sources), "total_steps": pipe.total_steps}
    st.pipe = st.table = None
    st.held = []
    del pipe


def walk_checks(st: State, ctx) -> List[common.Check]:
    e = ctx.config["embed"]
    paths, lengths, sources = st.window_walks
    if not paths:
        return [common.Check("window_walks", 0, -1)]
    paths = np.concatenate(paths).astype(np.int64)
    lengths = np.concatenate(lengths).astype(np.int64)
    sources = np.concatenate(sources).astype(np.int64)
    n = st.meta["n"]
    t = paths.shape[1]
    filled = paths >= 0
    prefix = np.arange(t)[None, :] < lengths[:, None]
    bad = ((paths[:, 0] != sources) | np.any(filled != prefix, axis=1)
           | (lengths < e["min_len"]) | (lengths > e["max_len"]))
    a, b = paths[:, :-1], paths[:, 1:]
    live = (a >= 0) & (b >= 0)
    keys = a[live] * n + b[live]
    pos = np.minimum(np.searchsorted(st.arc_keys, keys),
                     len(st.arc_keys) - 1)
    non_arcs = int(np.sum(st.arc_keys[pos] != keys))
    common.log(f"walk check walks={len(paths)} pairs={len(keys)} "
               f"mean_len={lengths.mean()}")
    return [common.Check("walk_faults", int(bad.sum()), 0),
            common.Check("non_arcs", non_arcs, 0),
            common.Check("ocn_off", st.ocn_off, 0)]


def reference_readings(st: State, ctx, dtype=np.float32, precision="highest",
                       groups=None):
    """The reference's losses and table changes over the first chunks,
    from its own tables, keys, schedule and alias table. ``groups`` keeps
    only that many lanes of each chunk: the half-batch fault."""
    import jax
    import jax.numpy as jnp

    e = ctx.config["embed"]
    n = st.meta["n"]
    key = jax.random.PRNGKey(reference.jax_seed(ctx.seed))
    _, key_train, rep = jax.random.split(key, 3)
    prob, alias = reference.alias_table(st.recount, e["neg_power"])
    prob, alias = jnp.asarray(prob), jnp.asarray(alias)
    chunk = reference.sgns_chunk_jit()
    norm = _norm_fn()
    dt = jnp.dtype(dtype)
    phi_in, phi_out = reference.init_tables(rep, n, e["dim"], dt)
    losses, readings = [], {}
    steps = len(st.first)
    with jax.default_matmul_precision(precision):
        for i, (walks, _) in enumerate(st.first):
            step = i * e["sync_period"]
            _, ck2 = chunk_keys(key_train, step, st.meta["total_steps"])
            lrs = lr_schedule(step, e["sync_period"], st.meta["total_steps"],
                              e["lr"], e["min_lr"])
            phi_in, phi_out, loss = chunk(
                phi_in, phi_out, jnp.asarray(walks[:, 0, :groups]), prob,
                alias, ck2,
                jnp.asarray(lrs), window=e["window"],
                negatives=e["negatives"])
            losses.append(float(jnp.sum(loss)))
            if i in (0, steps - 1):
                init_in, init_out = reference.init_tables(rep, n, e["dim"],
                                                          dt)
                readings[i] = (
                    float(norm(phi_in.astype(jnp.float32),
                               init_in.astype(jnp.float32))),
                    float(norm(phi_out.astype(jnp.float32),
                               init_out.astype(jnp.float32))))
                del init_in, init_out
    return losses, readings


def leaf_gap(got, want) -> float:
    """Worst leaf's |norm gap| over the larger of its reference norm and
    the median leaf's, leaves the reference leaves still excluded."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    med = float(np.median(want))
    keep = want >= 1e-3 * med
    scale = np.maximum(want, med)
    return float(np.max(np.abs(got - want)[keep] / scale[keep]))


def compare(losses, readings, losses_ref, readings_ref, limits
            ) -> List[common.Check]:
    """The DSGL numbers: the worst chunk's loss gap, and the worst leaf's
    change-norm gap after the first chunk and after the last."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, losses_ref))
    last = len(losses) - 1
    common.log(f"dsgl losses program={losses} reference={losses_ref}")
    common.log(f"dsgl change norms program={readings} "
               f"reference={readings_ref}")
    return [
        common.Check("loss_gap", loss_gap, limits["loss_gap"]),
        common.Check("step1_change_gap",
                     leaf_gap(readings[0], readings_ref[0]),
                     limits["step1_change_gap"]),
        common.Check("change_gap",
                     leaf_gap(readings[last], readings_ref[last]),
                     limits["change_gap"]),
    ]


def check(st: State, ctx) -> List[common.Check]:
    checks = walk_checks(st, ctx)
    t0 = time.perf_counter()
    losses_ref, readings_ref = reference_readings(st, ctx)
    common.log(f"reference took {time.perf_counter() - t0} s")
    losses = [loss for _, loss in st.first]
    return checks + compare(losses, st.readings, losses_ref, readings_ref,
                            ctx.traffic["limits"])
