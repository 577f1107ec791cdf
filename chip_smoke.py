"""On-chip smoke run of DistGER's embed -> refresh -> serve path.

One process, one TPU, the entry points a user calls, at the ``yt-sim``
preset (the paper's Table-2 YouTube shape: 1,138,499 nodes, average degree
5, R-MAT from a fixed seed) and the paper's embedding settings
(``PAPER_EMBED``: HuGE walks with information-centric termination, dim 128,
window 10, K=5, W=2, G=64):

  a. device   a TPU, or exit non-zero naming the platform found
  b. graph    R-MAT edges -> CSR -> HuGE common-neighbour counts (host)
  c. embed    StreamingEmbedPipeline; walk rounds capped at 2 (not 20)
  d. refresh  one ~1,000-edge churn batch through refresh_embedding
  e. serve    snapshot -> EmbedServer; top-K and pair queries vs the oracle
  f. kernel   one train_chunk through the Pallas SGNS kernel vs sgns_ref

    python chip_smoke.py              # phases a-f on one chip
    python chip_smoke.py --chips 4    # only the partition-sharded walk
                                      # exchange on a 4-device mesh vs the
                                      # stacked one-device emulation

Each line is ``<phase> <host|device> <seconds>s <fields>``: host set-up and
device work are timed apart, device work after ``block_until_ready``. A
failed check exits non-zero. The last line is one JSON object naming the
device as JAX reports it.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import link_prediction_auc  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.distger import GRAPH_PRESETS, PAPER_EMBED  # noqa: E402
from repro.core.api import (  # noqa: E402
    EmbedState, make_walk_plan, refresh_embedding)
from repro.core.dsgl import (  # noqa: E402
    DSGLConfig, build_alias_table, train_chunk)
from repro.core.incremental import IncrementalRefresh  # noqa: E402
from repro.core.walker import run_walk_batch  # noqa: E402
from repro.data.pipeline import ring_chunk_indices  # noqa: E402
from repro.graph.csr import build_csr  # noqa: E402
from repro.graph.generators import churn_batch, rmat_edges  # noqa: E402
from repro.runtime.serve import (  # noqa: E402
    EmbedServer, ServeConfig, oracle_scores, oracle_topk)
from repro.runtime.trainer import StreamingEmbedPipeline  # noqa: E402

SEED = 0
ROUNDS_CAP = 2            # walk rounds, against max_rounds=20 in the plan
CHURN_EDGES = 1000
QUERIES = 64              # per kind: top-K (k=10) and pair scoring
CANDIDATES = 100
SHARDED_BATCHES = 3       # 4096-source batches in the --chips 4 phase
# A collapsed or untrained embedding scores 0.5. Two rounds of HuGE walks
# train far past that; 0.75 leaves room for seed-to-seed spread while a
# real regression (NaN, collapse, no learning) misses it.
AUC_FLOOR = 0.75
# One train_chunk (50 lifetimes) through the kernel against sgns_ref, as
# ||d_kernel - d_ref|| / ||d_ref|| over the phi updates d. sgns_ref's plain
# f32 `@` runs at the backend's default matmul precision, which on TPU may
# round operands to bf16 (2^-9 relative); two chained matmuls per position
# give update errors of order 1e-2. 5e-2 holds that with margin and still
# fails a kernel that drops or doubles a term (an error of order 1). On a
# TPU v5e both paths ran at the default precision and agreed to 3e-7; each
# differed from sgns_ref at "highest" by 1.4e-3.
KERNEL_REL_TOL = 5e-2


def line(phase: str, where: str, seconds: float, **fields) -> None:
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"{phase:<9} {where:<6} {seconds:9.3f}s {kv}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def block(*xs):
    return jax.block_until_ready(xs)


# ---------------------------------------------------------------------------
# b. graph
# ---------------------------------------------------------------------------

def make_graph(num_nodes: int, avg_degree: int):
    """R-MAT at ``avg_degree`` = 2|E|/|V|. ``rmat_graph`` draws n*d edges
    and then symmetrises them, doubling the degree; this draws 1.1*n*d/2,
    the 10% covering the duplicates and self-loops R-MAT's skew makes."""
    t0 = time.perf_counter()
    edges = rmat_edges(num_nodes, int(1.1 * num_nodes * avg_degree / 2),
                       seed=SEED)
    graph = build_csr(edges, num_nodes)
    t_csr = time.perf_counter() - t0
    degree = graph.num_edges / num_nodes
    check(abs(degree - avg_degree) <= 0.1 * avg_degree,
          f"2|E|/|V| = {degree:.3f}, not within 10% of {avg_degree}")
    t0 = time.perf_counter()
    graph = graph.with_edge_cm()
    block(graph.edge_cm)
    isolated = int(np.sum(np.diff(np.asarray(graph.indptr)) == 0))
    line("b.graph", "host", t_csr, nodes=num_nodes, arcs=graph.num_edges,
         avg_degree=f"{degree:.4f}", isolated=isolated)
    line("b.graph", "host", time.perf_counter() - t0, step="with_edge_cm")
    return graph


# ---------------------------------------------------------------------------
# c. embed, and the ring checks d repeats
# ---------------------------------------------------------------------------

@jax.jit
def _bad_pairs(indptr, indices, walks):
    """Count consecutive walk pairs (a, b) that are not arcs: a lower-bound
    binary search of b in a's sorted CSR row. Returns (bad, pairs)."""
    a, b = walks[:, :-1], walks[:, 1:]
    live = (a >= 0) & (b >= 0)
    a = jnp.maximum(a, 0)
    lo, end = indptr[a], indptr[a + 1]
    last = indices.shape[0] - 1

    def step(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        go = (lo < hi) & (indices[jnp.minimum(mid, last)] < b)
        return jnp.where(go, mid + 1, lo), jnp.where(go | (lo >= hi), hi, mid)

    lo, _ = jax.lax.fori_loop(0, 32, step, (lo, end))
    found = (lo < end) & (indices[jnp.minimum(lo, last)] == b)
    return jnp.sum(live & ~found), jnp.sum(live)


@jax.jit
def _recount(counts, walks):
    """counts + per-node occurrences in ``walks`` (-1 padded)."""
    n = counts.shape[0]
    ids = jnp.where(walks >= 0, walks, n).reshape(-1)
    return counts + jnp.zeros(n + 1, jnp.int32).at[ids].add(1)[:n]


def retired_counts(pipe, rounds: int) -> jax.Array:
    """Occurrence counts of the walk rounds the ring no longer holds,
    walked again. ``ocn`` counts every round ever appended, and a ring of
    ``ring_rounds`` rounds (its ~0.5 GB budget holds one at yt-sim size)
    overwrites the oldest on wrap; under vertex-keyed RNG a round's key
    reproduces its walks exactly."""
    counts = jnp.zeros(pipe.graph.num_nodes, jnp.int32)
    for r in range(max(rounds - pipe.ring_rounds, 0)):
        key = jax.random.fold_in(pipe.key_walk, r)
        for start in range(0, len(pipe.sources), pipe.walker_batch):
            src = jnp.asarray(pipe.sources[start:start + pipe.walker_batch])
            st = run_walk_batch(pipe.graph, src, key, pipe.policy, pipe.spec)
            counts = _recount(counts, st.path)
    return counts


def check_ring(pipe, counts0, spec, phase: str) -> None:
    t0 = time.perf_counter()
    ring, graph = pipe.ring, pipe.graph
    bad, pairs = _bad_pairs(graph.indptr, graph.indices, ring.walks)
    recount = _recount(counts0, ring.walks)
    ocn_off = jnp.sum(recount != ring.ocn)
    stored = jnp.sum(ring.walks >= 0, axis=1)
    len_off = jnp.sum(stored != ring.lengths)
    bad, pairs, ocn_off, len_off = (int(x) for x in block(
        bad, pairs, ocn_off, len_off))
    lengths = np.asarray(ring.lengths)
    deg = np.diff(np.asarray(graph.indptr))[np.asarray(ring.walks[:, 0])]
    walked, stuck = lengths[deg > 0], lengths[deg == 0]
    line(phase, "device", time.perf_counter() - t0, step="ring_checks",
         pairs=pairs, non_arcs=bad, ocn_mismatch=ocn_off,
         length_mismatch=len_off,
         len_range=f"[{walked.min()},{walked.max()}]",
         mean_len=f"{walked.mean():.3f}", isolated_walks=len(stuck))
    check(bad == 0, f"{bad} ring pairs are not arcs of the graph")
    check(ocn_off == 0, f"ring ocn differs from a recount at {ocn_off} nodes")
    check(len_off == 0, f"{len_off} ring lengths differ from their walks")
    check(walked.min() >= spec.min_len and walked.max() <= spec.max_len,
          f"walk lengths [{walked.min()}, {walked.max()}] outside "
          f"[{spec.min_len}, {spec.max_len}]")
    check(np.all(stuck == 1), "a walk from an isolated vertex moved")


def chunk_loss(pipe, phi_in, phi_out, idx, table) -> float:
    """SGNS loss of one fixed chunk at (phi_in, phi_out): train_chunk at
    lr 0 leaves phi as it was and returns the chunk's losses."""
    cfg = pipe.cfg
    c = idx.shape[0]
    _, _, losses = train_chunk(
        jnp.copy(phi_in), jnp.copy(phi_out), pipe.ring.walks[idx], table,
        jnp.zeros(0, jnp.int32), jax.random.PRNGKey(SEED + 1),
        jnp.zeros(c, jnp.float32), cfg.window, cfg.negatives, False, False)
    return float(jnp.sum(losses))


def embed(graph):
    cfg = dataclasses.replace(PAPER_EMBED, rng_mode="vertex")
    policy, spec, rounds = make_walk_plan(cfg)
    plan_max = rounds["max_rounds"]
    rounds = dict(rounds, min_rounds=ROUNDS_CAP, max_rounds=ROUNDS_CAP)
    dsgl = DSGLConfig(dim=cfg.dim, window=cfg.window, negatives=cfg.negatives,
                      epochs=cfg.epochs, lr=cfg.lr,
                      multi_windows=cfg.multi_windows, seed=cfg.seed)
    t0 = time.perf_counter()
    pipe = StreamingEmbedPipeline(graph, policy, spec, rounds, dsgl,
                                  num_shards=1)
    phi0 = block(jnp.copy(pipe.phi_in), jnp.copy(pipe.phi_out))
    n = graph.num_nodes
    per_round = pipe.steps_per_round
    chunk = min(dsgl.sync_period, per_round)
    line("c.embed", "host", time.perf_counter() - t0, step="pipeline_init",
         rounds_cap=f"{ROUNDS_CAP}(plan:{plan_max})",
         walk_batches_per_round=math.ceil(n / pipe.walker_batch),
         ring_slots=pipe.ring.capacity,
         steps=f"{ROUNDS_CAP}x{per_round}",
         dispatches=ROUNDS_CAP * math.ceil(per_round / chunk),
         table_gb=f"{2 * pipe.phi_in.nbytes / 1e9:.3f}",
         ring_gb=f"{pipe.ring.walks.nbytes / 1e9:.3f}")

    t0 = time.perf_counter()
    res = pipe.run()
    block(pipe.phi_in, pipe.phi_out, pipe.ring.walks)
    line("c.embed", "device", time.perf_counter() - t0, step="run",
         rounds=res["rounds"], steps=res["steps"],
         supersteps=int(res["stats"]["supersteps"]),
         mean_len=f"{res['stats']['mean_len']:.3f}")
    check(res["rounds"] == ROUNDS_CAP, f"ran {res['rounds']} walk rounds")
    check(res["steps"] == ROUNDS_CAP * per_round,
          f"trained {res['steps']} lifetime steps")

    t0 = time.perf_counter()
    counts0 = block(retired_counts(pipe, res["rounds"]))[0]
    line("c.embed", "device", time.perf_counter() - t0,
         step="rewalk_retired_rounds_for_ocn_reference",
         rounds=max(res["rounds"] - pipe.ring_rounds, 0))
    check_ring(pipe, counts0, spec, "c.embed")

    t0 = time.perf_counter()
    idx = ring_chunk_indices(jax.random.PRNGKey(SEED + 2), 0, n, chunk, 1,
                             dsgl.batch_groups, dsgl.multi_windows)
    table = build_alias_table(np.asarray(pipe.ring.ocn), dsgl.neg_power)
    before = chunk_loss(pipe, *phi0, idx, table)
    after = chunk_loss(pipe, pipe.phi_in, pipe.phi_out, idx, table)
    del phi0
    finite = bool(jnp.all(jnp.isfinite(pipe.phi_in))
                  & jnp.all(jnp.isfinite(pipe.phi_out)))
    line("c.embed", "device", time.perf_counter() - t0, step="loss",
         chunk_loss_init=f"{before:.1f}", chunk_loss_trained=f"{after:.1f}",
         phi_finite=finite)
    check(finite, "phi has non-finite entries")
    check(after < before, "the chunk loss did not fall")

    t0 = time.perf_counter()
    phi_in, _ = pipe.embeddings()
    auc = link_prediction_auc(graph, phi_in, np.random.default_rng(SEED))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    line("c.embed", "host", time.perf_counter() - t0, step="auc",
         auc=f"{auc:.4f}", floor=AUC_FLOOR,
         peak_bytes_in_use=peak)
    check(auc >= AUC_FLOOR, f"link-prediction AUC {auc:.4f} < {AUC_FLOOR}")
    return pipe, cfg, counts0


# ---------------------------------------------------------------------------
# d. refresh
# ---------------------------------------------------------------------------

def refresh(pipe, cfg, counts0):
    t0 = time.perf_counter()
    und = pipe.graph.num_edges // 2
    batch = churn_batch(pipe.graph, frac=CHURN_EDGES / und, seed=SEED)
    state = EmbedState(refresher=IncrementalRefresh(pipe), cfg=cfg,
                       num_shards=1)
    line("d.refresh", "host", time.perf_counter() - t0, step="churn_batch",
         inserts=len(batch.insert), deletes=len(batch.delete))
    t0 = time.perf_counter()
    phi_in, phi_out, stats = refresh_embedding(state, batch)
    finite = bool(np.isfinite(phi_in).all() and np.isfinite(phi_out).all())
    line("d.refresh", "device", time.perf_counter() - t0,
         affected_frac=f"{stats.affected_frac:.6f}",
         affected=stats.affected, rewalk_walks=stats.rewalk_walks,
         extra_rounds=stats.extra_rounds,
         fine_tune_steps=stats.fine_tune_steps, phi_finite=finite)
    check(finite, "phi has non-finite entries after the refresh")
    check(stats.changed_edges == len(batch.insert) + len(batch.delete),
          "the refresh did not take the whole churn batch")
    check_ring(pipe, counts0, pipe.spec, "d.refresh")


# ---------------------------------------------------------------------------
# e. serve
# ---------------------------------------------------------------------------

def serve(pipe):
    n = pipe.graph.num_nodes
    rng = np.random.default_rng(SEED)
    live = np.flatnonzero(np.diff(np.asarray(pipe.graph.indptr)) > 0)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        t0 = time.perf_counter()
        pipe.save(root)
        server = EmbedServer(ServeConfig(batch_slots=8))
        check(server.offer_snapshot(root), "the server refused the snapshot")
        line("e.serve", "host", time.perf_counter() - t0,
             step="save+offer_snapshot", version=server.active_version())
    users = rng.choice(live, 2 * QUERIES, replace=False)
    cands = rng.integers(0, n, (QUERIES, CANDIDATES))
    t0 = time.perf_counter()
    top = [server.submit(int(u), k=10) for u in users[:QUERIES]]
    pair = [server.submit(int(u), c) for u, c in zip(users[QUERIES:], cands)]
    check(None not in top + pair, "the server shed a query")
    server.drain()
    line("e.serve", "device", time.perf_counter() - t0,
         queries=2 * QUERIES, batch_slots=8, **{
             k: server.stats()[k] for k in ("served", "latency_p50_s",
                                            "latency_p99_s")})

    t0 = time.perf_counter()
    phi = server.active_phi()
    diffs, ids_off = [], 0
    for qid, u in zip(top, users[:QUERIES]):
        resp = server.responses[qid]
        want, want_ids = oracle_topk(phi, int(u), 10)
        diffs.append(np.abs(resp.scores - want))
        # Ids may swap only where oracle scores tie within rounding.
        got_oracle = oracle_scores(phi, int(u), resp.ids)
        ids_off += int(np.sum(np.abs(got_oracle - want) > 0))
    for qid, u, c in zip(pair, users[QUERIES:], cands):
        diffs.append(np.abs(server.responses[qid].scores
                            - oracle_scores(phi, int(u), c)))
    diffs = np.concatenate(diffs)
    off = int(np.sum(diffs > 0))
    line("e.serve", "host", time.perf_counter() - t0, step="oracle",
         scores=len(diffs), mismatched=off, max_abs_diff=float(diffs.max()),
         topk_id_mismatch=ids_off)
    check(off == 0 and ids_off == 0,
          f"{off} scores differ from the oracle (max |diff| "
          f"{float(diffs.max())}), {ids_off} top-K ids differ")


# ---------------------------------------------------------------------------
# f. kernel
# ---------------------------------------------------------------------------

def _rel(a, b) -> float:
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(a, b))
    den = sum(float(jnp.sum(y ** 2)) for y in b)
    return math.sqrt(num / max(den, 1e-30))


def kernel(pipe):
    cfg = pipe.cfg
    n = pipe.graph.num_nodes
    c = cfg.sync_period
    idx = ring_chunk_indices(jax.random.PRNGKey(SEED + 3), 0, n, c, 1,
                             cfg.batch_groups, cfg.multi_windows)
    walks = pipe.ring.walks[idx]
    table = build_alias_table(np.asarray(pipe.ring.ocn), cfg.neg_power)
    rows = jnp.zeros(0, jnp.int32)
    key = jax.random.PRNGKey(SEED + 4)
    lrs = jnp.full((c,), cfg.lr, jnp.float32)
    base = (pipe.phi_in, pipe.phi_out)

    def run(use_kernel, precision=None):
        args = (jnp.copy(base[0]), jnp.copy(base[1]), walks, table, rows, key,
                lrs, cfg.window, cfg.negatives, use_kernel, False)
        t0 = time.perf_counter()
        with jax.default_matmul_precision(precision):
            compiled = train_chunk.lower(*args).compile()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = block(*compiled(*args[:7]))
        upd = (out[0] - base[0], out[1] - base[1])
        return upd, out[2], compiled.as_text(), t_compile, \
            time.perf_counter() - t0

    d_ker, l_ker, text, tc, tr = run(True)
    custom = "tpu_custom_call" in text
    line("f.kernel", "device", tr, path="pallas", precision="default",
         compile_s=f"{tc:.3f}", tpu_custom_call=custom,
         chunk=f"{c}x64x2x100 d={cfg.dim}")
    d_ref, l_ref, _, tc, tr = run(False)
    line("f.kernel", "device", tr, path="sgns_ref", precision="default",
         compile_s=f"{tc:.3f}")
    d_hi, _, _, tc, tr = run(False, "highest")
    line("f.kernel", "device", tr, path="sgns_ref", precision="highest",
         compile_s=f"{tc:.3f}")
    rel = _rel(d_ker, d_ref)
    rel_hi = _rel(d_ker, d_hi)
    rel_ref = _rel(d_ref, d_hi)
    loss_rel = float(jnp.abs(jnp.sum(l_ker) - jnp.sum(l_ref))
                     / jnp.abs(jnp.sum(l_ref)))
    line("f.kernel", "device", 0.0, step="compare",
         rel_kernel_vs_ref=f"{rel:.3e}", tol=KERNEL_REL_TOL,
         rel_kernel_vs_ref_highest=f"{rel_hi:.3e}",
         rel_ref_vs_ref_highest=f"{rel_ref:.3e}",
         loss_rel=f"{loss_rel:.3e}")
    check(custom, "tpu_custom_call is missing: the kernel did not compile "
          "for the chip")
    check(rel <= KERNEL_REL_TOL and loss_rel <= KERNEL_REL_TOL,
          f"kernel vs sgns_ref: update {rel:.3e}, loss {loss_rel:.3e} "
          f"> {KERNEL_REL_TOL}")


# ---------------------------------------------------------------------------
# --chips 4: the partition-sharded walk exchange
# ---------------------------------------------------------------------------

def sharded_walks(graph):
    from repro.core.mpgp import mpgp_partition
    from repro.core.shard_engine import (
        make_walk_mesh, partitioned_csr_for, run_walk_sharded)

    mesh = make_walk_mesh(4)      # raises unless 4 devices are present
    t0 = time.perf_counter()
    asn = mpgp_partition(graph, 4).assignment
    line("g.shard", "host", time.perf_counter() - t0, step="mpgp_k4",
         owned=np.bincount(asn, minlength=4).tolist())
    cfg = dataclasses.replace(PAPER_EMBED, rng_mode="vertex")
    policy, spec, _ = make_walk_plan(cfg)
    part = jnp.asarray(asn, jnp.int32)
    key = jax.random.PRNGKey(SEED)
    batch = 4096
    for i in range(SHARDED_BATCHES):
        src = jnp.arange(i * batch, (i + 1) * batch, dtype=jnp.int32)
        runs = {}
        for name, m in (("mesh", mesh), ("stacked", None)):
            t0 = time.perf_counter()
            st = run_walk_sharded(graph, src, key, policy, spec, part, 4,
                                  mesh=m, engine="local", transport="a2a")
            block(st.path)
            runs[name] = (st, time.perf_counter() - t0)
        (sm, tm), (se, te) = runs["mesh"], runs["stacked"]
        same = (np.array_equal(np.asarray(sm.path), np.asarray(se.path))
                and np.array_equal(np.asarray(sm.info.L),
                                   np.asarray(se.info.L))
                and int(sm.msg_count) == int(se.msg_count))
        exact = all(float(s.msg_bytes) == float(s.msg_bytes_analytic)
                    for s in (sm, se))
        line("g.shard", "device", tm, batch=i, engine="local+a2a",
             mesh_s=f"{tm:.3f}", stacked_s=f"{te:.3f}",
             msg_count=int(sm.msg_count), msg_bytes=float(sm.msg_bytes),
             msg_bytes_analytic=float(sm.msg_bytes_analytic),
             bit_identical=same)
        check(same, f"batch {i}: mesh and stacked walks differ")
        check(exact, f"batch {i}: measured bytes differ from analytic")
    pcsr = partitioned_csr_for(graph, asn, 4, mesh=mesh)
    devices = {sh.device for sh in pcsr.slices.indices.addressable_shards}
    line("g.shard", "device", 0.0, step="placement",
         slice_devices=len(devices),
         slice_shape=list(pcsr.slices.indices.addressable_shards[0]
                          .data.shape))
    check(len(devices) == 4, f"CSR slices live on {len(devices)} devices")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = p.parse_args(argv)

    t_start = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    line("a.device", "host", time.perf_counter() - t_start,
         kind=repr(dev.device_kind), count=len(jax.devices()),
         jax=jax.__version__,
         jaxlib=importlib.metadata.version("jaxlib"),
         libtpu=importlib.metadata.version("libtpu"), compile_cache=cache)

    preset = GRAPH_PRESETS["yt-sim"]
    graph = make_graph(preset.num_nodes, preset.avg_degree)
    if args.chips == 4:
        sharded_walks(graph)
    else:
        pipe, cfg, counts0 = embed(graph)
        refresh(pipe, cfg, counts0)
        serve(pipe)
        kernel(pipe)
    line("total", "host", time.perf_counter() - t_start,
         compile_s=f"{compile_s[0]:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
