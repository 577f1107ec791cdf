"""Benchmark harness entry point: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Writes per-benchmark JSON artifacts under benchmarks/artifacts/ and prints
a summary line per benchmark. The dry-run/roofline artifacts (launch.dryrun)
live in benchmarks/artifacts/dryrun/.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from benchmarks import (
    classification, e2e, generality, incom_bench, incremental, partitioning,
    recovery, scaling, serve, sync_bytes, train_efficiency, walk_efficiency,
)

BENCHES = {
    "e2e": e2e.run,                           # Fig. 5
    "scaling": scaling.run,                   # Fig. 6/7
    "walk_efficiency": walk_efficiency.run,   # Fig. 10(a)
    "train_efficiency": train_efficiency.run, # Fig. 10(b)
    "partitioning": partitioning.run,         # Fig. 10(c,d), Table 5, Fig. 11
    "incom": incom_bench.run,                 # §3.1 O(1) vs O(L)
    "sync_bytes": sync_bytes.run,             # §4.2-III
    "generality": generality.run,             # Fig. 12
    "classification": classification.run,     # Fig. 9
    "incremental": incremental.run,           # dynamic-graph refresh (PR 4)
    "recovery": recovery.run,                 # fault-tolerance MTTR (PR 6)
    "serve": serve.run,                       # embedding read path (PR 10)
}

REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def _walk_summary() -> dict:
    """Walker supersteps/s + cross-partition message volume on a small
    partitioned corpus — the walk half of the BENCH_train trajectory.

    The timing runs the dense engine (the k=1 instantiation of the BSP
    program) so ``supersteps_per_s`` stays comparable with the numbers
    recorded before the sharded refactor; the message fields come from one
    4-shard run of the same workload, where they are MEASURED from the
    exchanged tensors."""
    import numpy as np
    import jax
    from repro.core.transition import make_policy
    from repro.core.walker import WalkSpec, batch_stats, run_walk_batch
    from repro.graph.generators import rmat_graph

    g = rmat_graph(2048, 10, seed=3).with_edge_cm()
    part = np.arange(g.num_nodes) % 4
    spec = WalkSpec(max_len=80, min_len=8, mu=0.995, info_mode="incom",
                    reg_start=16)
    sources = np.arange(512, dtype=np.int32) % g.num_nodes
    policy = make_policy("huge")
    import jax.numpy as jnp
    st = run_walk_batch(g, jnp.asarray(sources), jax.random.PRNGKey(0),
                        policy, spec)
    jax.block_until_ready(st.path)                        # compile + warm
    best = float("inf")
    for r in range(3):
        t0 = time.time()
        st = run_walk_batch(g, jnp.asarray(sources), jax.random.PRNGKey(r),
                            policy, spec)
        jax.block_until_ready(st.path)
        best = min(best, time.time() - t0)
    stats = batch_stats(st)
    st4 = run_walk_batch(g, jnp.asarray(sources), jax.random.PRNGKey(0),
                         policy, spec, jnp.asarray(part, jnp.int32))
    stats4 = batch_stats(st4)
    return {
        "supersteps_per_s": stats["supersteps"] / best,
        "msg_count": stats4["msg_count"],
        "msg_bytes": stats4["msg_bytes"],
        "msg_bytes_analytic": stats4["msg_bytes_analytic"],
    }


def _emit_bench_walk(walk_rec: dict) -> None:
    """Repo-root BENCH_walk.json: the sharded-engine trajectory — stacked
    supersteps/s at k=1/k=4, measured-vs-analytic message bytes, and the
    walk→train overlap efficiency of the fused streaming pipeline."""
    sharded = walk_rec.get("sharded", {})
    full_csr = walk_rec.get("full_csr_bytes")
    scaling = {}
    for key in ("k1_local", "k2_local", "k4_local", "k4_local_degree_tau",
                "k8_local", "k16_local"):
        row = sharded.get(key)
        if not row:
            continue
        scaling[key] = {
            "supersteps_per_s": row.get("supersteps_per_s"),
            "msg_bytes_per_shard": row.get("msg_bytes_per_shard", 0.0),
            "peak_shard_csr_bytes": row.get("csr_bytes_per_shard"),
            "csr_frac_of_full": (
                row.get("csr_bytes_per_shard") / full_csr
                if full_csr and row.get("csr_bytes_per_shard") else None),
            "peak_lane_occupancy": row.get("peak_lane_occupancy"),
            "pool_slots": row.get("pool_slots"),
            "msg_bytes_measured": row.get("msg_bytes_measured"),
            "msg_bytes_analytic": row.get("msg_bytes_analytic"),
        }
    bench = {
        "engine": {
            "supersteps_per_s_k1": sharded.get("k1_dense", {}).get("supersteps_per_s"),
            "supersteps_per_s_k1_bsp": sharded.get("k1_bsp", {}).get("supersteps_per_s"),
            "supersteps_per_s_k4": sharded.get("k4", {}).get("supersteps_per_s"),
            "supersteps_per_s_k4_local": sharded.get("k4_local", {}).get(
                "supersteps_per_s"),
            "msg_bytes_measured_k4": sharded.get("k4", {}).get("msg_bytes_measured"),
            "msg_bytes_analytic_k4": sharded.get("k4", {}).get("msg_bytes_analytic"),
            "bytes_per_msg_k4": sharded.get("k4", {}).get("bytes_per_msg"),
        },
        # Partition-local engine scaling columns (CSR slices + lane pools +
        # packed exchange). peak_shard_csr_bytes tracks the (|V|+|E|)/k
        # partition model; supersteps/s is the 1-device STACKED EMULATION,
        # which serializes the k per-shard programs — it measures per-shard
        # program cost, not multi-machine wall-clock (DESIGN.md §9).
        "scaling_local": scaling,
        "scaling_note": (
            "supersteps_per_s in scaling_local is the single-device stacked "
            "EMULATION (k per-shard programs serialized on one CPU); the "
            "partition-local engine's scaling wins are the memory and wire "
            "columns (peak_shard_csr_bytes, msg_bytes_per_shard). On a real "
            "k-device mesh each program runs in parallel on its own slice."),
        "full_csr_bytes": full_csr,
        "overlap": walk_rec.get("overlap", {}),
        "per_superstep_growth": {
            "incom": walk_rec.get("growth_incom"),
            "fullpath": walk_rec.get("growth_fullpath"),
        },
        # Same workload as the BENCH_train walk summary (512 walkers on the
        # 2048-node rmat), reusing the measurements walk_efficiency already
        # took rather than re-benchmarking.
        "seed_workload": {
            "supersteps_per_s": sharded.get("k1_dense", {}).get(
                "supersteps_per_s"),
            "msg_count": sharded.get("k4", {}).get("msg_count"),
            "msg_bytes": sharded.get("k4", {}).get("msg_bytes_measured"),
            "msg_bytes_analytic": sharded.get("k4", {}).get(
                "msg_bytes_analytic"),
        },
    }
    # Frozen reference: the single-device engine's number recorded by the
    # previous PR's BENCH_train run (if present on this checkout).
    train_path = os.path.join(REPO_ROOT, "BENCH_train.json")
    if os.path.exists(train_path):
        with open(train_path) as f:
            prev = json.load(f)
        ref = prev.get("walk", {}).get("supersteps_per_s")
        bench["engine"]["seed_reference_supersteps_per_s"] = ref
        k1 = bench["engine"].get("supersteps_per_s_k1")
        if ref and k1:
            bench["engine"]["k1_vs_seed"] = k1 / ref
    # ISSUE 3 acceptance tracker: k=4 against 2x the pre-refactor 1.8k.
    k4_prev = 1767.9
    k4_now = bench["engine"].get("supersteps_per_s_k4")
    bench["k4_target"] = {
        "baseline_prev_pr": k4_prev,
        "target_2x": 2 * k4_prev,
        "measured_replicated": k4_now,
        "measured_local_emulation": bench["engine"].get(
            "supersteps_per_s_k4_local"),
        "speedup_vs_prev": (k4_now / k4_prev) if k4_now else None,
        "met": bool(k4_now and k4_now >= 2 * k4_prev),
    }
    path = os.path.join(REPO_ROOT, "BENCH_walk.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, default=float)
    print(f"wrote {path}", flush=True)


def _emit_bench_train(train_rec: dict) -> None:
    """Repo-root BENCH_train.json: train + walk efficiency trajectory so
    perf regressions are visible in review from this PR onward."""
    bench = {
        "train": {
            "steps_per_s_fused": train_rec.get("steps_per_s_fused"),
            "steps_per_s_seed": train_rec.get("steps_per_s_seed"),
            "speedup_fused_vs_seed": train_rec.get("speedup_fused_vs_seed"),
            "residency_nodes": train_rec.get("residency_nodes"),
            "nodes_per_s": train_rec.get("nodes_per_s"),
        },
        "walk": _walk_summary(),
    }
    path = os.path.join(REPO_ROOT, "BENCH_train.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, default=float)
    print(f"wrote {path}", flush=True)


def _emit_bench_incremental(rec: dict) -> None:
    """Repo-root BENCH_incremental.json: the dynamic-graph cost/quality
    trajectory — churn %, affected-vertex %, re-walk supersteps vs a full
    recompute, refresh wall-clock vs from-scratch, and the AUC columns
    (stale / refreshed / scratch) on the mutated graph."""
    bench = {
        "workload": {
            "num_nodes": rec.get("num_nodes"),
            "churn_edges": rec.get("churn_edges"),
            "churn_frac": rec.get("churn_frac"),
        },
        "cost": {
            "affected_vertices": rec.get("affected_vertices"),
            "affected_frac": rec.get("affected_frac"),
            "retained_rounds": rec.get("retained_rounds"),
            "extra_rounds": rec.get("extra_rounds"),
            "rewalk_walks": rec.get("rewalk_walks"),
            "scratch_walks": rec.get("scratch_walks"),
            "rewalk_walk_frac": rec.get("rewalk_walk_frac"),
            "rewalk_supersteps": rec.get("rewalk_supersteps"),
            "scratch_walk_supersteps": rec.get("scratch_walk_supersteps"),
            "rewalk_superstep_frac": rec.get("rewalk_superstep_frac"),
            "fine_tune_steps": rec.get("fine_tune_steps"),
            "refresh_wall_s": rec.get("refresh_wall_s"),
            "scratch_recompute_wall_s": rec.get("scratch_recompute_wall_s"),
            "refresh_speedup_vs_scratch": rec.get(
                "refresh_speedup_vs_scratch"),
        },
        "quality": {
            "auc_stale": rec.get("auc_stale"),
            "auc_refresh": rec.get("auc_refresh"),
            "auc_scratch": rec.get("auc_scratch"),
            "auc_delta_vs_scratch": rec.get("auc_delta_vs_scratch"),
            "auc_gain_vs_stale": rec.get("auc_gain_vs_stale"),
        },
        # ISSUE 4 acceptance tracker: <=30% of vertices re-walked, AUC
        # within 0.02 of the from-scratch recompute on the mutated graph.
        "acceptance": {
            # Explicit defaults, not `or`: 0.0 is a PASSING value for
            # both metrics and must not be coerced to the failing 1.0.
            "affected_le_30pct": bool(rec.get("affected_frac", 1.0)
                                      <= 0.30),
            "auc_within_002": bool(abs(rec.get("auc_delta_vs_scratch", 1.0))
                                   <= 0.02),
        },
    }
    path = os.path.join(REPO_ROOT, "BENCH_incremental.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, default=float)
    print(f"wrote {path}", flush=True)


def _emit_bench_recovery(rec: dict) -> None:
    """Repo-root BENCH_recovery.json: the fault-tolerance trajectory —
    MTTR of snapshot-resume vs from-scratch recompute, the snapshot tax,
    WAL replay wall-clock vs churn backlog, and the self-healing
    degraded-mode rows (DESIGN.md §12) under the logged chaos seed."""
    bench = {
        "workload": {"num_nodes": rec.get("num_nodes")},
        "mttr": {
            "resume_s": rec.get("mttr_resume_s"),
            "scratch_s": rec.get("mttr_scratch_s"),
            "speedup": rec.get("mttr_speedup"),
            "resume_bit_identical": rec.get("resume_bit_identical"),
        },
        "snapshot": {
            "bytes": rec.get("snapshot_bytes"),
            "overhead_frac": rec.get("snapshot_overhead_frac"),
            "wall_ckpt_s": rec.get("wall_ckpt_s"),
            "wall_scratch_s": rec.get("wall_scratch_s"),
        },
        "wal_replay": rec.get("wal_replay"),
        # Self-healing degraded modes (nightly chaos job artifact): the
        # fault schedule is randomized by REPRO_CHAOS_SEED (logged here).
        "chaos_seed": rec.get("chaos_seed"),
        "degraded": {
            "watchdog": rec.get("watchdog"),
            "elastic": rec.get("elastic"),
            "ingest_slo": rec.get("ingest_slo"),
        },
        # ISSUE 6 acceptance tracker: resuming from the last snapshot must
        # beat a from-scratch recompute by >= 3x, and the resumed run must
        # reproduce the uninterrupted run bit-for-bit. ISSUE 8 adds: a
        # NaN divergence heals (rollback) onto the fault-free trajectory,
        # and an elastic k-1 continuation stays bit-identical to the
        # fault-free k-shard run.
        "acceptance": {
            "resume_ge_3x": bool(rec.get("mttr_speedup", 0.0) >= 3.0),
            "bit_identical": bool(rec.get("resume_bit_identical", False)),
            "watchdog_healed_bit_identical": bool(
                (rec.get("watchdog") or {}).get("healed_bit_identical",
                                                False)),
            "elastic_bit_identical_to_k4": bool(
                (rec.get("elastic") or {}).get("bit_identical_to_k4",
                                               False)),
        },
    }
    path = os.path.join(REPO_ROOT, "BENCH_recovery.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, default=float)
    print(f"wrote {path}", flush=True)


def _emit_bench_serve(rec: dict) -> None:
    """Repo-root BENCH_serve.json: the embedding read path under chaos —
    queries/s + tail latency of the slot-pool wave scheduler, and the
    availability / served-version / freshness mix across a churn run with
    snapshot swaps, a refresh retry storm, one torn candidate step, and a
    swap-window fault drill (DESIGN.md §14)."""
    bench = {
        "workload": {
            "num_nodes": rec.get("num_nodes"),
            "dim": rec.get("dim"),
            "churn_rounds": rec.get("churn_rounds"),
        },
        "throughput": {
            "queries_per_s": rec.get("queries_per_s"),
            "latency_p50_s": rec.get("latency_p50_s"),
            "latency_p99_s": rec.get("latency_p99_s"),
        },
        "availability": {
            "offered": rec.get("queries_offered"),
            "admitted": rec.get("queries_admitted"),
            "served": rec.get("queries_served"),
            "availability": rec.get("availability"),
            "shed": rec.get("shed"),
        },
        "versioning": {
            "swaps": rec.get("swaps"),
            "served_by_version": rec.get("served_by_version"),
            "served_by_freshness": rec.get("served_by_freshness"),
        },
        "chaos": {
            "ingest_retries": rec.get("ingest_retries"),
            "refresh_deaths": rec.get("refresh_deaths"),
            "refresh_faults_fired": rec.get("refresh_faults_fired"),
            "swap_faults_fired": rec.get("swap_faults_fired"),
        },
        "oracle": {
            "mismatches": rec.get("oracle_mismatches"),
            "topk_checked": rec.get("oracle_topk_checked"),
            "topk_mismatches": rec.get("oracle_topk_mismatches"),
            "bit_identical": rec.get("oracle_bit_identical"),
        },
        # ISSUE 10 acceptance tracker: >= 99% of admitted queries answered
        # across >= 3 swaps under the chaos schedule, and every response
        # bit-identical to the NumPy oracle of its stamped version.
        "acceptance": {
            "availability_ge_99pct": bool(
                rec.get("availability", 0.0) >= 0.99),
            "swaps_ge_3": bool(rec.get("swaps", 0) >= 3),
            "oracle_bit_identical": bool(
                rec.get("oracle_bit_identical", False)),
        },
    }
    path = os.path.join(REPO_ROOT, "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(bench, f, indent=1, default=float)
    print(f"wrote {path}", flush=True)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--full", action="store_true",
                   help="larger graphs (slower)")
    p.add_argument("--only", default=None)
    args = p.parse_args()
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    names = [args.only] if args.only else list(BENCHES)
    failures = 0
    for name in names:
        t0 = time.time()
        print(f"=== benchmark: {name} ===", flush=True)
        try:
            rec = BENCHES[name](quick=not args.full)
            dt = time.time() - t0
            summary = {k: v for k, v in rec.items()
                       if isinstance(v, (int, float, str))}
            print(f"    done in {dt:.1f}s :: "
                  f"{json.dumps(summary, default=float)[:300]}", flush=True)
            if name == "train_efficiency" and args.only == name:
                _emit_bench_train(rec)
            if name == "walk_efficiency" and args.only == name:
                _emit_bench_walk(rec)
            if name == "incremental" and args.only == name:
                _emit_bench_incremental(rec)
            if name == "recovery" and args.only == name:
                _emit_bench_recovery(rec)
            if name == "serve" and args.only == name:
                _emit_bench_serve(rec)
        except Exception as e:
            failures += 1
            print(f"    FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    print(f"\n{len(names) - failures}/{len(names)} benchmarks succeeded")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
