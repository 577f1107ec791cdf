"""Fault-tolerant trainers: the LM trainer (sharded step, checkpoint/
restart, failure injection, straggler-mitigated input pipeline) and the
device-resident DSGL embedding trainer.

The LM step function is the same one the dry-run lowers (launch/steps.py);
this module adds the *runtime* posture around it:

* step-granular checkpoints (params + opt state + data cursor + RNG),
  atomic commit, restore-and-continue is bit-exact (tested);
* ``FailureInjector`` raises a simulated node failure at a chosen step;
  ``run_with_restarts`` demonstrates the restart loop a cluster agent
  would drive — resume from the latest checkpoint, replay nothing;
* data fetches go through ``BackupShardFetcher`` (speculative backup after
  a deadline) so one slow host does not stall the step (straggler policy).

``DSGLTrainer`` is the embedding-side runtime: per-shard walk streams
assemble (C, S, G, W, T) chunks on a prefetch thread while the device runs
the previous chunk's fused ``train_chunk`` scan — host work and device
work overlap, and the device never waits on per-step negative sampling or
uploads (the NOMAD overlap argument, on one process).

``StreamingEmbedPipeline`` fuses the two halves of DistGER end to end:
the partition-sharded walk engine appends finished rounds into a
device-resident ``CorpusRing`` and the DSGL learner consumes ring slots as
stacked shard chunks via one device gather — walks never round-trip
through host numpy, and round r+1's walk generation is dispatched before
round r's training so the two overlap (walk rounds stay gated by the
Eq. 7 ΔD controller). See DESIGN.md §9.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt.checkpoint import (
    latest_step, load_checkpoint, restore_into, save_checkpoint,
)
from repro.common.logging import get_logger, log_context
from repro.data.pipeline import BackupShardFetcher, TokenStream
from repro.models import zoo
from repro.models.config import ModelConfig
from repro.optim.optimizers import AdamWConfig, init_opt_state, opt_update
from repro.optim.schedules import cosine_warmup

# The fault-injection machinery lives in repro.runtime.faults; the names
# are re-exported here because this module introduced them (existing tests
# and callers import them from repro.runtime.trainer).
from repro.runtime.faults import (            # noqa: F401  (re-export)
    NULL_INJECTOR, FailureInjector, FaultInjector, SimulatedFailure,
)

log = get_logger("repro.runtime.trainer")


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 20
    ckpt_every: int = 5
    ckpt_dir: str = "/tmp/repro_ckpt"
    batch: int = 4
    seq_len: int = 64
    lr: float = 3e-4
    warmup: int = 10
    seed: int = 0
    straggler_deadline_s: float = 5.0


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, schedule):
    """(params, opt_state, batch, step) -> (params, opt_state, metrics)."""
    loss_of = zoo.loss_fn(cfg)

    def step_fn(params, opt_state, batch, step):
        lr = schedule(step)
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        params, opt_state, gnorm = opt_update(
            grads, opt_state, params, opt_cfg, lr)
        return params, opt_state, {"loss": loss, "gnorm": gnorm, "lr": lr}

    return jax.jit(step_fn, donate_argnums=(0, 1))


class Trainer:
    def __init__(self, model_cfg: ModelConfig, tcfg: TrainerConfig,
                 injector: Optional[FailureInjector] = None,
                 delay_injector: Optional[Callable[[int], float]] = None):
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.injector = injector or FailureInjector()
        opt_cfg = AdamWConfig(moment_dtype=model_cfg.opt_state_dtype)
        self.opt_cfg = opt_cfg
        self.schedule = cosine_warmup(tcfg.lr, tcfg.warmup, tcfg.steps)
        self.step_fn = make_train_step(model_cfg, opt_cfg, self.schedule)

        stream = TokenStream(
            vocab_size=model_cfg.vocab_size, batch_per_shard=tcfg.batch,
            seq_len=tcfg.seq_len, seed=tcfg.seed)
        self.fetcher = BackupShardFetcher(
            primary=stream.batch_at, backup=stream.batch_at,
            deadline_s=tcfg.straggler_deadline_s,
            delay_injector=delay_injector)
        self.metrics_log: list = []

    # --- state ----------------------------------------------------------------
    def init_state(self):
        key = jax.random.PRNGKey(self.tcfg.seed)
        params = zoo.init_params(key, self.model_cfg)
        opt_state = init_opt_state(params, self.opt_cfg)
        return {"params": params, "opt": opt_state}

    def save(self, state, step: int):
        save_checkpoint(self.tcfg.ckpt_dir, step, state,
                        meta={"data_step": step, "seed": self.tcfg.seed})

    def try_restore(self, template):
        last = latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return None, 0
        _, arrays, meta = load_checkpoint(self.tcfg.ckpt_dir, last)
        state = restore_into(template, arrays)
        return state, int(meta["data_step"])

    # --- loops ----------------------------------------------------------------
    def run(self, start_state=None, start_step: int = 0) -> Dict[str, Any]:
        """Run to completion or until an (injected) failure propagates."""
        state = start_state if start_state is not None else self.init_state()
        step = start_step
        while step < self.tcfg.steps:
            self.injector.check(step)
            batch_np = self.fetcher.fetch(step)
            if "labels" in batch_np and self.model_cfg.encdec:
                batch_np = dict(batch_np)
                batch_np["frames"] = np.random.default_rng(step).normal(
                    size=(self.tcfg.batch, self.tcfg.seq_len // 2,
                          self.model_cfg.d_model)).astype(np.float32)
            batch = jax.tree_util.tree_map(jnp.asarray, batch_np)
            params, opt, metrics = self.step_fn(
                state["params"], state["opt"], batch, jnp.int32(step))
            state = {"params": params, "opt": opt}
            self.metrics_log.append(
                {k: float(v) for k, v in metrics.items()} | {"step": step})
            step += 1
            if step % self.tcfg.ckpt_every == 0 or step == self.tcfg.steps:
                self.save(state, step)
        return {"state": state, "final_step": step,
                "metrics": self.metrics_log,
                "straggler_stats": self.fetcher.stats}

    def run_with_restarts(self, max_restarts: int = 4) -> Dict[str, Any]:
        """The cluster-agent loop: restart from the latest checkpoint on
        failure. Demonstrates end-to-end checkpoint/restart fault tolerance."""
        template = self.init_state()
        restarts = 0
        while True:
            state, start = self.try_restore(template)
            if state is None:
                state, start = template, 0
            try:
                out = self.run(start_state=state, start_step=start)
                out["restarts"] = restarts
                return out
            except SimulatedFailure:
                restarts += 1
                if restarts > max_restarts:
                    raise


# ---------------------------------------------------------------------------
# Device-resident DSGL embedding trainer
# ---------------------------------------------------------------------------


class DSGLTrainer:
    """Chunked, prefetched driver around ``core.dsgl.train_chunk``.

    Host side: one ``WalkCorpusStream`` per shard replica; a ``Prefetcher``
    thread stacks the next (C, S, G, W, T) chunk while the device runs the
    current one. Device side: stacked replica matrices stay resident across
    the whole run — per chunk there is exactly one walk upload, one fused
    scan over C lifetimes (negatives drawn in-jit from the alias table) and,
    in the sharded regime, one hotness-row exchange.
    """

    def __init__(self, walks_rank: np.ndarray, order, cfg,
                 *, num_shards: int = 1, prefetch_depth: int = 2):
        from repro.core import sync as sync_mod
        from repro.core.dsgl import build_alias_table, init_embeddings
        from repro.data.pipeline import WalkCorpusStream, stacked_shard_chunk

        self.cfg = cfg
        self.num_shards = num_shards
        self.order = order
        self.chunk = max(cfg.sync_period, 1)
        self.streams = [
            WalkCorpusStream(
                walks=walks_rank, group_size=cfg.batch_groups,
                multi_windows=cfg.multi_windows, seed=cfg.seed,
                shard_id=s, num_shards=num_shards)
            for s in range(num_shards)
        ]
        self._stack = stacked_shard_chunk
        self._sync = sync_mod
        self.starts, self.ends = order.hotness_blocks()
        self.neg_table = build_alias_table(order.sorted_ocn, cfg.neg_power)
        self.prefetch_depth = prefetch_depth

        n = len(order.to_rank)
        key = jax.random.PRNGKey(cfg.seed)
        keys = jax.random.split(key, num_shards + 1)
        self.key = keys[0]
        reps = [init_embeddings(n, cfg.dim, k) for k in keys[1:]]
        self.phi_in = jnp.stack([r[0] for r in reps])
        self.phi_out = jnp.stack([r[1] for r in reps])

    def steps_per_epoch(self) -> int:
        return min(s.steps_per_epoch() for s in self.streams)

    def _lrs(self, global_step: int, count: int, total: int) -> jnp.ndarray:
        fracs = (global_step + np.arange(count)) / max(total, 1)
        return jnp.asarray(
            np.maximum(self.cfg.lr * (1.0 - fracs), self.cfg.min_lr),
            jnp.float32)

    def run(self) -> Dict[str, Any]:
        from repro.core.dsgl import train_chunk
        from repro.data.pipeline import Prefetcher

        cfg = self.cfg
        spe = self.steps_per_epoch()
        total = cfg.epochs * spe
        rng = np.random.default_rng(cfg.seed)

        # Chunk schedule clamped at epoch boundaries (each epoch is its own
        # shuffle; a chunk must not wrap into re-trained duplicates of the
        # previous epoch or overrun the configured step count).
        schedule = [
            (epoch, step0, min(step0 + self.chunk, spe) - step0)
            for epoch in range(cfg.epochs)
            for step0 in range(0, spe, self.chunk)
        ]

        def fetch(chunk_idx: int) -> np.ndarray:
            epoch, step0, count = schedule[chunk_idx % len(schedule)]
            return self._stack(self.streams, epoch, step0, count)

        prefetcher = Prefetcher(fetch, depth=self.prefetch_depth)
        losses: list = []
        t0 = time.perf_counter()
        sync_bytes = 0.0
        do_sync = self.num_shards > 1
        try:
            for c, (epoch, step0, count) in enumerate(schedule):
                _, chunk_np = prefetcher.next()
                wb = jnp.asarray(chunk_np)
                rows = (jnp.asarray(self._sync.sample_hotness_rows(
                    self.starts, self.ends, rng), jnp.int32)
                    if do_sync else jnp.zeros(0, jnp.int32))
                self.key, sub = jax.random.split(self.key)
                with obs.phase("dsgl.chunk"):
                    self.phi_in, self.phi_out, loss = train_chunk(
                        self.phi_in, self.phi_out, wb, self.neg_table, rows,
                        sub, self._lrs(epoch * spe + step0, count, total),
                        cfg.window, cfg.negatives, cfg.use_kernel, do_sync)
                losses.append(loss)
                if do_sync:
                    sync_bytes += float(
                        rows.size * cfg.dim * 4 * self.num_shards * 2)
                obs.inc("train.steps", count)
        finally:
            prefetcher.close()
        jax.block_until_ready(self.phi_in)
        wall = time.perf_counter() - t0
        steps = total
        obs.set_gauge("train.steps_per_s", steps / max(wall, 1e-9))
        obs.set_gauge("train.sync_bytes", sync_bytes)
        return {
            "steps": steps,
            "steps_per_s": steps / max(wall, 1e-9),
            "loss": [float(v) for v in
                     np.concatenate([np.asarray(l).reshape(-1)
                                     for l in losses])],
            "sync_bytes": sync_bytes,
            "wall_s": wall,
        }

    def embeddings(self):
        """(phi_in, phi_out) in rank space, replica-averaged."""
        if self.num_shards > 1:
            return (jnp.mean(self.phi_in, axis=0),
                    jnp.mean(self.phi_out, axis=0))
        return self.phi_in[0], self.phi_out[0]


# ---------------------------------------------------------------------------
# Fused walk→train streaming pipeline
# ---------------------------------------------------------------------------


class StreamingEmbedPipeline:
    """partition-sharded walks → device corpus ring → DSGL, overlapped.

    Per round r the host (1) syncs once on the (|V|,) occurrence counts —
    the Eq. 7 controller input (gated on a WINDOWED-mean ΔD when
    ``rounds_cfg["window"]`` > 1, which keeps tight deltas from pinning
    small-graph runs at max_rounds on sampling noise — DESIGN.md §9), also
    reused to rebuild the node-space negative alias table and the hotness
    blocks; (2) if the controller says continue, DISPATCHES round r+1's
    walks; (3) enqueues round r's training chunks, whose (C, S, G, W, T)
    input is one device gather from the ring
    (``data.pipeline.ring_chunk_indices``). Walks therefore never
    leave the device between sampler and learner, and on a multi-device
    mesh the walk shards compute round r+1 while the trainer replicas run
    round r (on one device the queues interleave; the host never stalls).

    Embeddings stay in NODE space (no rank relabeling is needed because
    the frequency order evolves with the stream); hotness-block sync rows
    are mapped rank→node per round. The learning-rate schedule is fixed a
    priori at ``epochs * max_rounds * steps_per_round`` steps — the walk
    controller decides the corpus, not the schedule — and after sampling
    stops the pipeline keeps consuming re-shuffled ring slots until the
    schedule completes (the word2vec single-decayed-pass convention,
    §6.4 recipe).

    ``overlap=False`` serializes the phases (block after every walk round
    and every train call) — the baseline the walk→train overlap-efficiency
    benchmark compares against.
    """

    def __init__(self, graph, policy, spec, rounds_cfg: Dict, dsgl_cfg,
                 *, assignment: Optional[np.ndarray] = None,
                 num_shards: int = 1, walker_batch: int = 4096,
                 overlap: bool = True, health=None):
        from repro.core.corpus import CorpusRing
        from repro.core.dsgl import init_embeddings
        from repro.core.termination import WalkCountController

        if getattr(policy, "needs_edge_cm", False) and graph.edge_cm is None:
            graph = graph.with_edge_cm()
        self.graph = graph
        self.policy = policy
        self.spec = spec
        self.cfg = dsgl_cfg
        self.num_shards = max(num_shards, 1)
        # Walk-dispatch shard count. It starts equal to the DSGL replica
        # count but the two are independent degrees of freedom: elastic
        # reconfiguration drops walk_shards to k-1 when a shard dies while
        # the (S, N, d) replica stack — a TRAINING ensemble choice baked
        # into phi's shape — stays at S.
        self.walk_shards = self.num_shards
        self.assignment = (None if assignment is None
                           else jnp.asarray(assignment, jnp.int32))
        self.walker_batch = walker_batch
        self.overlap = overlap
        # Self-healing runtime state (DESIGN.md §12): the optional health
        # watchdog, the divergence-rollback lr multiplier (persisted — a
        # backed-off run resumes backed off), and the elastic-reconfig log.
        self.health = health
        self._lr_scale = 1.0
        self._reconfigs: list = []
        self._faults: FaultInjector = NULL_INJECTOR
        # Snapshot hand-off hooks (DESIGN.md §14): called with
        # (path, seq, meta) after every COMMITTED snapshot — the serving
        # side subscribes here to learn that a new candidate version
        # exists. Never called for torn/crashed writes.
        self._snapshot_hooks: list = []
        self.controller = WalkCountController(**rounds_cfg)
        self.degrees = np.asarray(graph.degrees(), dtype=np.int64)

        n = graph.num_nodes
        self.sources = np.arange(n, dtype=np.int32)
        # Retain as many full rounds as fit a ~0.5 GB slot budget; older
        # rounds retire on wrap (training reads the current round's slots
        # plus, in the tail, whatever is retained; ocn accumulates across
        # wraps). One round is the floor — the round-aligned slot map needs
        # it resident — so a graph whose single round cannot fit the int32
        # occurrence guard must use the host-spilling two-phase path.
        budget_rounds = max(1, (1 << 27) // max(spec.max_len * n, 1))
        self.ring_rounds = min(self.controller.max_rounds, budget_rounds)
        if self.ring_rounds * n * spec.max_len >= 2**31:
            raise ValueError(
                f"one walk round (|V|={n} x max_len={spec.max_len}) exceeds "
                "the device corpus-ring budget; use "
                "embed_graph(streaming=False), which spills rounds to host")
        self.ring = CorpusRing.create(self.ring_rounds * n, spec.max_len, n)
        per = dsgl_cfg.batch_groups * dsgl_cfg.multi_windows
        self.steps_per_round = max(n // self.num_shards // per, 1)
        self.total_steps = (dsgl_cfg.epochs * self.controller.max_rounds
                            * self.steps_per_round)
        self.global_step = 0

        key = jax.random.PRNGKey(dsgl_cfg.seed)
        self.key_walk, self.key_train, *rep_keys = jax.random.split(
            key, 2 + self.num_shards)
        reps = [init_embeddings(n, dsgl_cfg.dim, k) for k in rep_keys]
        self.phi_in = jnp.stack([r[0] for r in reps])      # (S, N, d)
        self.phi_out = jnp.stack([r[1] for r in reps])
        # Device-accumulated walk stats: summed without forcing a sync.
        self._stats = {k: jnp.zeros(()) for k in (
            "supersteps", "accepts", "rejects", "msg_count", "msg_bytes",
            "msg_bytes_analytic")}
        self._ft = None       # (start_step, total, lr0) fine-tune schedule
        # Host mirror of the ring layout: which ROOT VERTEX and which walk
        # ROUND each slot currently holds (-1 = never written). Maintained
        # from the host-known dispatch chunks — no device sync — so the
        # incremental refresh can locate every resident walk of an
        # affected vertex (and the round key that produced it) even after
        # partial extra rounds or ring wraps, where slot arithmetic fails.
        self._slot_root = np.full(self.ring.capacity, -1, np.int64)
        self._slot_round = np.full(self.ring.capacity, -1, np.int64)
        self._cursor = 0
        self._rounds_walked = 0
        # Crash-consistent run cursor (all persisted by ``save``): the run
        # loop is a state machine over phase ∈ rounds → tail → done with
        # ``_trained_rounds`` counting fully-trained rounds, so ``resume``
        # re-enters the exact round the snapshot committed and replays
        # forward deterministically (round keys are fold_in(key_walk, r),
        # training keys fold_in(key_train, global_step)).
        self._rounds_cfg = dict(rounds_cfg)
        self._trained_rounds = 0
        self._phase = "rounds"          # rounds | tail | done
        self._ckpt_seq = 0              # snapshot numbering (monotonic)
        self._ckpt_root: Optional[str] = None
        self._ckpt_every = 0
        self._ckpt_tick = 0
        self._ckpt_keep: Optional[int] = None

    # --- walk side --------------------------------------------------------
    def _run_round(self, r: int, sources: Optional[np.ndarray] = None,
                   faults: FaultInjector = NULL_INJECTOR):
        """Dispatch all walk batches of round r; returns async
        (chunk_sources, state) pairs.

        Under vertex-keyed RNG every chunk of a round shares the ROUND key
        (lane draws are disambiguated by source-vertex id, not position),
        which is what lets the incremental refresh re-walk an arbitrary
        subset of sources later and reproduce this round's walks
        bit-for-bit without knowing the original chunk boundaries.

        ``faults`` fires the ``superstep`` injection point at every chunk
        dispatch — the host boundary where a crash interrupts a round with
        some walks computed but nothing committed to the ring; recovery
        simply re-dispatches the whole round under its original key.
        """
        from repro.core.walker import run_walk_batch

        if sources is None:
            sources = self.sources
        by_vertex = self.spec.rng_mode == "vertex"
        round_key = jax.random.fold_in(self.key_walk, r)
        pairs = []
        with obs.trace_span("walk.round", round=r, walks=len(sources)):
            for start in range(0, len(sources), self.walker_batch):
                faults.fire("superstep", f"round {r} chunk @{start}")
                chunk = np.asarray(sources[start:start + self.walker_batch])
                k = (round_key if by_vertex
                     else jax.random.fold_in(round_key, start))
                pairs.append((chunk, run_walk_batch(
                    self.graph, jnp.asarray(chunk, jnp.int32), k,
                    self.policy, self.spec, self.assignment,
                    num_shards=self.walk_shards
                    if self.assignment is not None else None)))
                obs.inc("walk.batches")
            obs.inc("walk.dispatched", len(sources))
        return pairs

    def _append(self, pairs, round_idx: int):
        # Donated: the old ring version is dropped right here; XLA aliases
        # the buffers when no queued trainer gather still reads them and
        # falls back to a copy when one does — either way no per-batch
        # full-ring copy survives on the steady-state hot path.
        from repro.core.corpus import ring_append_donated
        cap = self.ring.capacity
        for chunk, st in pairs:
            self.ring = ring_append_donated(
                self.ring, st.path, st.info.L.astype(jnp.int32))
            slots = (self._cursor + np.arange(len(chunk))) % cap
            self._slot_root[slots] = chunk
            self._slot_round[slots] = round_idx
            self._cursor = int((self._cursor + len(chunk)) % cap)
            for k in self._stats:
                self._stats[k] = self._stats[k] + getattr(st, k)

    # --- train side -------------------------------------------------------
    def _lrs(self, count: int) -> jnp.ndarray:
        # _lr_scale is the divergence-rollback backoff multiplier (1.0
        # until the watchdog ever trips; exact-1.0 multiply is bit-neutral).
        if self._ft is not None:
            start, total, lr0 = self._ft     # fine-tune mini-schedule
            fracs = (self.global_step - start + np.arange(count)) / max(
                total, 1)
            return jnp.asarray(
                np.maximum(lr0 * self._lr_scale * (1.0 - fracs),
                           self.cfg.min_lr),
                jnp.float32)
        fracs = (self.global_step + np.arange(count)) / max(self.total_steps, 1)
        return jnp.asarray(
            np.maximum(self.cfg.lr * self._lr_scale * (1.0 - fracs),
                       self.cfg.min_lr),
            jnp.float32)

    def _train_slots(self, base: int, pool: int, ocn_host: np.ndarray,
                     steps: int, table=None, order=None):
        """Enqueue ``steps`` training steps over ring slots [base, base+pool).

        ``table``/``order`` let callers whose ocn is frozen (the schedule
        tail) reuse one alias-table/argsort build across calls instead of
        redoing the O(N) host work per iteration."""
        from repro.core.corpus import FrequencyOrder
        from repro.core.dsgl import (
            build_alias_table, train_chunk, train_chunk_checked,
        )
        from repro.core.sync import sample_hotness_rows
        from repro.data.pipeline import ring_chunk_indices

        cfg = self.cfg
        if table is None:
            table = build_alias_table(ocn_host, cfg.neg_power)  # node space
        replicated = self.num_shards > 1
        rng = np.random.default_rng(cfg.seed * 9176 + self.global_step)
        if order is None:
            order = FrequencyOrder.from_ocn(ocn_host) if replicated else None
        chunk = max(min(cfg.sync_period, steps), 1)
        done = 0
        while done < steps:
            count = min(chunk, steps - done)
            # Improvement-III cadence: one hotness exchange per sync_period
            # LIFETIMES (global steps), not per dispatched chunk — rounds
            # are often much shorter than a sync period, and averaging the
            # replicas every few steps collapses the diversity that makes
            # the replica ensemble train well (measured: AUC 0.64 -> 0.86).
            sync_now = replicated and (
                self.global_step // cfg.sync_period
                != (self.global_step + count) // cfg.sync_period)
            ck = jax.random.fold_in(self.key_train, self.global_step)
            idx = ring_chunk_indices(
                ck, base, pool, count, self.num_shards,
                cfg.batch_groups, cfg.multi_windows)
            wb = self.ring.walks[idx]                     # (C,S,G,W,T) gather
            if sync_now:
                starts, ends = order.hotness_blocks()
                rows_rank = sample_hotness_rows(starts, ends, rng)
                rows = jnp.asarray(order.to_node[rows_rank], jnp.int32)
            else:
                rows = jnp.zeros(0, jnp.int32)
            ck2 = jax.random.fold_in(self.key_train, 2 * self.total_steps
                                     + self.global_step)
            lrs = self._lrs(count)
            # Divergence corruption sites (watchdog tests/chaos sweeps):
            # poison a few phi rows with NaN, or blow the chunk lr up —
            # both produce REAL divergences for the watchdog to catch.
            if self._faults.inject("phi_nan"):
                self.phi_in = self.phi_in.at[:, :4, :].set(jnp.nan)
            if self._faults.inject("lr_spike"):
                lrs = lrs * 1e4
            check = (self.health is not None
                     and self.health.due(self.global_step, count))
            with obs.phase("dsgl.chunk"):
                if check:
                    self.phi_in, self.phi_out, _, hs = train_chunk_checked(
                        self.phi_in, self.phi_out, wb, table, rows, ck2,
                        lrs, cfg.window, cfg.negatives,
                        cfg.use_kernel, sync_now)
                else:
                    self.phi_in, self.phi_out, _ = train_chunk(
                        self.phi_in, self.phi_out, wb, table, rows, ck2,
                        lrs, cfg.window, cfg.negatives,
                        cfg.use_kernel, sync_now)
            self.global_step += count
            done += count
            obs.inc("train.steps", count)
            if check:
                # One host pull of 5 scalars; raises DivergenceError on a
                # verdict — run()'s heal loop owns the reaction.
                self.health.observe(
                    {k: v for k, v in hs.items()},
                    step=self.global_step, count=count,
                    slots=np.unique(np.asarray(idx)))

    # --- driver -----------------------------------------------------------
    def run(self, *, ckpt_root: Optional[str] = None,
            ckpt_every_rounds: int = 0,
            ckpt_keep: Optional[int] = None,
            faults: FaultInjector = NULL_INJECTOR,
            liveness=None) -> Dict[str, Any]:
        """Run (or CONTINUE, after ``resume``) the walk→train lifecycle.

        The loop is a state machine over persisted cursors (see ``save``):
        phase ``rounds`` iterates round r = ``_trained_rounds`` with the
        invariant that rounds 0..r are appended and the ΔD gate holds r
        decisions; phase ``tail`` re-consumes the frozen ring until the
        a-priori schedule completes. A snapshot taken at any iteration
        boundary is therefore a consistent cut, and because every source of
        randomness is keyed off persisted state (round keys
        fold_in(key_walk, r), train keys fold_in(key_train, global_step),
        hotness rng seeded by global_step), a resumed run replays the
        remaining rounds/chunks bit-identically to the uninterrupted one.

        ``ckpt_root``/``ckpt_every_rounds`` enable periodic snapshots (one
        every N round/tail iterations plus a final one); ``ckpt_keep``
        bounds retention (older snapshots are pruned after each commit);
        ``faults`` is the injection harness (production default never
        fires).

        Self-healing (DESIGN.md §12): when a ``HealthMonitor`` is attached
        the training chunks run watchdog reductions at its cadence, and a
        divergence verdict rolls the pipeline back to the last consistent
        snapshot, backs the learning rate off, quarantines (re-walks) the
        offending ring slots, and re-enters this state machine — bounded
        by ``HealthConfig.max_rollbacks``. When a ``LivenessProbe`` is
        passed, every round boundary polls shard liveness and a
        persistently-dead walk shard triggers ``elastic_reconfigure``
        (continue at k-1 shards) instead of stalling the round.
        """
        from repro.runtime.health import DivergenceError

        t0 = time.perf_counter()
        self._ckpt_root, self._ckpt_every = ckpt_root, ckpt_every_rounds
        self._ckpt_keep = ckpt_keep
        self._faults = faults
        try:
            if (self.health is not None and ckpt_root
                    and latest_step(ckpt_root) is None):
                # The watchdog needs a rollback base before the first
                # divergence can possibly be detected.
                self.save(ckpt_root, faults=faults)
            while True:
                try:
                    result = self._run_phases(faults, liveness)
                    break
                except DivergenceError as err:
                    self._heal_divergence(err, faults)
        finally:
            self._faults = NULL_INJECTOR
        result["wall_s"] = time.perf_counter() - t0
        return result

    def _run_phases(self, faults: FaultInjector, liveness) -> Dict[str, Any]:
        from repro.core.info import relative_entropy_dpq

        n = len(self.sources)
        if self._phase == "rounds":
            if self._rounds_walked == 0:
                self._append(self._run_round(0, faults=faults), 0)
                self._rounds_walked = 1
            while True:
                r = self._trained_rounds
                with log_context(round=r):
                    faults.fire("round", r)
                    self._poll_liveness(liveness, faults)
                    ocn_host = np.asarray(self.ring.ocn)  # per-round sync
                    cont = self.controller.update_d(
                        relative_entropy_dpq(self.degrees, ocn_host))
                    if cont and self.overlap:
                        nxt = self._run_round(r + 1, faults=faults)  # ∥ train
                    self._train_slots((r * n) % self.ring.capacity, n,
                                      ocn_host, self.steps_per_round)
                    if not self.overlap:
                        jax.block_until_ready(self.phi_in)
                    self._trained_rounds = r + 1
                    if not cont:
                        break
                    if not self.overlap:
                        nxt = self._run_round(r + 1, faults=faults)
                        jax.block_until_ready(nxt[-1][1].path)
                    self._append(nxt, r + 1)
                    self._rounds_walked = r + 2
                    self._maybe_snapshot(faults)
            self._phase = "tail"
            obs.span_event("pipeline.phase", phase="tail",
                           round=self._trained_rounds,
                           step=self.global_step)
            self._maybe_snapshot(faults)

        if self._phase == "tail":
            # Schedule-completion tail: re-consume the filled ring until
            # the a-priori lr schedule ends (extra decayed passes over the
            # corpus). ocn is frozen now, so the alias table / frequency
            # order are built once and reused across every tail iteration
            # (and rebuilt identically on resume — they are pure functions
            # of the persisted ring.ocn).
            from repro.core.corpus import FrequencyOrder
            from repro.core.dsgl import build_alias_table

            ocn_host = np.asarray(self.ring.ocn)
            filled = self.ring.num_filled
            tail_table = build_alias_table(ocn_host, self.cfg.neg_power)
            tail_order = (FrequencyOrder.from_ocn(ocn_host)
                          if self.num_shards > 1 else None)
            while self.global_step < self.total_steps:
                faults.fire("tail", self.global_step)
                self._train_slots(
                    0, filled, ocn_host,
                    min(self.steps_per_round,
                        self.total_steps - self.global_step),
                    table=tail_table, order=tail_order)
                self._maybe_snapshot(faults)
            jax.block_until_ready(self.phi_in)
            self._phase = "done"
            obs.span_event("pipeline.phase", phase="done",
                           step=self.global_step)
            if self._ckpt_root and self._ckpt_every:
                self.save(self._ckpt_root, faults=faults)   # final snapshot

        phi_in, phi_out = self.embeddings(as_numpy=False)
        stats = {k: float(v) for k, v in self._stats.items()}
        stats["mean_len"] = (float(np.asarray(self.ring.lengths).sum())
                             / max(self.ring.num_filled, 1))
        stats["d_history"] = list(self.controller.history)
        # Export the walk-engine accumulators exactly where the run loop
        # already pulled them to host — no extra device syncs.
        if obs.enabled():
            for k in self._stats:
                obs.set_gauge(f"walk.{k}", stats[k])
            obs.set_gauge("walk.mean_len", stats["mean_len"])
            obs.set_gauge("walk.rounds", self.controller.rounds)
            obs.set_gauge("train.global_step", self.global_step)
        return {
            "phi_in": phi_in, "phi_out": phi_out,
            "rounds": self.controller.rounds,
            "steps": self.global_step,
            "ring": self.ring,
            "stats": stats,
            "health": (self.health.report()
                       if self.health is not None else None),
            "reconfigs": list(self._reconfigs),
            "lr_scale": float(self._lr_scale),
        }

    # --- crash-consistent snapshots (DESIGN.md §11) ------------------------
    def _maybe_snapshot(self, faults: FaultInjector) -> None:
        if not self._ckpt_root or not self._ckpt_every:
            return
        self._ckpt_tick += 1
        if self._ckpt_tick % self._ckpt_every == 0:
            self.save(self._ckpt_root, faults=faults)

    def _state_tree(self) -> Dict[str, Any]:
        from repro.core.corpus import ring_export

        tree: Dict[str, Any] = {
            "phi_in": self.phi_in,
            "phi_out": self.phi_out,
            "ring": ring_export(self.ring),
            "slot_root": self._slot_root,
            "slot_round": self._slot_round,
            "key_walk": self.key_walk,
            "key_train": self.key_train,
            "stats": dict(self._stats),
            "graph": {"indptr": self.graph.indptr,
                      "indices": self.graph.indices},
        }
        if self.graph.weights is not None:
            tree["graph"]["weights"] = self.graph.weights
        if self.graph.edge_cm is not None:
            tree["graph"]["edge_cm"] = self.graph.edge_cm
        if self.assignment is not None:
            tree["assignment"] = self.assignment
        return tree

    def save(self, root: str, *, faults: FaultInjector = NULL_INJECTOR,
             meta_extra: Optional[Dict[str, Any]] = None) -> str:
        """Checkpoint the COMPLETE walk→train state as one atomic
        ``repro.ckpt`` tree: phi replicas, the corpus ring (walks, lengths,
        ocn, cursor — lossless), the host slot→root/slot→round maps, both
        RNG keys, the ΔD controller history, the run cursors, the MPGP
        assignment, and the graph's CSR arrays (so recovery needs no
        external graph handle and restores the exact mutated topology).

        ``faults`` can crash the write two ways: the ``ckpt_write`` point
        fires before anything is written (the snapshot is simply lost) and
        ``torn("ckpt")`` commits the directory, then corrupts its manifest
        before raising — the committed-but-unsynced-data crash the reader
        fallback in ``ckpt.checkpoint`` exists for.
        """
        from repro.graph.delta import graph_version

        with obs.trace_span("ckpt.write", seq=self._ckpt_seq,
                            round=self._trained_rounds,
                            step=self.global_step, phase=self._phase):
            return self._save_inner(root, faults, meta_extra,
                                    graph_version)

    def _save_inner(self, root, faults, meta_extra, graph_version) -> str:
        faults.fire("ckpt_write", self._ckpt_seq)
        torn = faults.torn("ckpt")
        meta = {
            "kind": "streaming_pipeline",
            "global_step": int(self.global_step),
            "cursor": int(self._cursor),
            "rounds_walked": int(self._rounds_walked),
            "trained_rounds": int(self._trained_rounds),
            "phase": self._phase,
            "controller": self.controller.to_state(),
            "rounds_cfg": self._rounds_cfg,
            "total_steps": int(self.total_steps),
            "num_shards": int(self.num_shards),
            "walk_shards": int(self.walk_shards),
            "lr_scale": float(self._lr_scale),
            "walker_batch": int(self.walker_batch),
            "overlap": bool(self.overlap),
            "graph_version": int(graph_version(self.graph)),
        }
        if meta_extra:
            meta.update(meta_extra)
        path = save_checkpoint(root, self._ckpt_seq, self._state_tree(),
                               meta=meta)
        if torn:
            with open(os.path.join(path, "manifest.json"), "w") as f:
                f.write('{"step": ')          # data blocks never hit disk
            raise SimulatedFailure(
                f"torn checkpoint write at snapshot {self._ckpt_seq}")
        with log_context(round=self._trained_rounds,
                         graph_version=meta["graph_version"]):
            log.info("snapshot %d committed at %s (phase=%s step=%d)",
                     self._ckpt_seq, path, self._phase, self.global_step)
        obs.inc("ckpt.writes")
        obs.set_gauge("ckpt.last_seq", self._ckpt_seq)
        seq = self._ckpt_seq
        self._ckpt_seq += 1
        if self._ckpt_keep:
            from repro.ckpt.checkpoint import prune_steps
            prune_steps(root, self._ckpt_keep)
        for hook in self._snapshot_hooks:
            hook(path, seq, meta)
        return path

    def add_snapshot_hook(self, hook) -> None:
        """Subscribe ``hook(path, seq, meta)`` to committed snapshots —
        the serve-side hand-off (an ``EmbedServer`` offer, a replication
        push). Hooks run AFTER the atomic commit and after retention
        pruning, so the path they see is durable."""
        self._snapshot_hooks.append(hook)

    @classmethod
    def resume(cls, root: str, policy, spec, dsgl_cfg, *,
               step: Optional[int] = None,
               rounds_cfg: Optional[Dict] = None,
               walker_batch: Optional[int] = None,
               overlap: Optional[bool] = None,
               health=None) -> "StreamingEmbedPipeline":
        """Rebuild a pipeline from the newest VALID snapshot under ``root``
        (or an explicit ``step``) and re-enter its exact cursor state.

        The caller re-provides the non-serializable plan objects (policy,
        spec, dsgl config — the same posture as ``Trainer.try_restore``
        rebuilding from the model config); everything mutable, including
        the graph itself, comes out of the checkpoint. Call ``run()`` on
        the result to continue — the rounds/chunks past the cursor
        re-dispatch under their original round keys, so the finished
        embedding is bit-identical to the uninterrupted run's.
        """
        from repro.core.corpus import ring_import
        from repro.core.termination import WalkCountController
        from repro.graph.csr import CSRGraph

        step_loaded, arrays, meta = load_checkpoint(root, step)
        if meta.get("kind") != "streaming_pipeline":
            raise ValueError(
                f"checkpoint at {root} step {step_loaded} is not a "
                "streaming-pipeline snapshot")
        graph = CSRGraph(
            indptr=jnp.asarray(arrays["graph/indptr"], jnp.int32),
            indices=jnp.asarray(arrays["graph/indices"], jnp.int32),
            weights=(jnp.asarray(arrays["graph/weights"], jnp.float32)
                     if "graph/weights" in arrays else None),
            edge_cm=(jnp.asarray(arrays["graph/edge_cm"], jnp.int32)
                     if "graph/edge_cm" in arrays else None),
        )
        pipe = cls(
            graph, policy, spec,
            rounds_cfg if rounds_cfg is not None else meta["rounds_cfg"],
            dsgl_cfg,
            assignment=arrays.get("assignment"),
            num_shards=int(meta["num_shards"]),
            walker_batch=(walker_batch if walker_batch is not None
                          else int(meta["walker_batch"])),
            overlap=(overlap if overlap is not None
                     else bool(meta["overlap"])),
            health=health)
        ring = ring_import({k: arrays[f"ring/{k}"] for k in
                            ("walks", "lengths", "ocn", "cursor", "total")})
        if ring.capacity != pipe.ring.capacity:
            raise ValueError(
                f"snapshot ring capacity {ring.capacity} does not match "
                f"the rebuilt pipeline's {pipe.ring.capacity}; resume with "
                "the original rounds_cfg/spec")
        pipe.ring = ring
        pipe.phi_in = jnp.asarray(arrays["phi_in"], jnp.float32)
        pipe.phi_out = jnp.asarray(arrays["phi_out"], jnp.float32)
        pipe.key_walk = jnp.asarray(arrays["key_walk"])
        pipe.key_train = jnp.asarray(arrays["key_train"])
        pipe._stats = {k: jnp.asarray(arrays[f"stats/{k}"])
                       for k in pipe._stats}
        pipe._slot_root = np.asarray(arrays["slot_root"], np.int64)
        pipe._slot_round = np.asarray(arrays["slot_round"], np.int64)
        pipe.controller = WalkCountController.from_state(meta["controller"])
        pipe.global_step = int(meta["global_step"])
        pipe.total_steps = int(meta["total_steps"])
        pipe._cursor = int(meta["cursor"])
        pipe._rounds_walked = int(meta["rounds_walked"])
        pipe._trained_rounds = int(meta["trained_rounds"])
        pipe._phase = meta["phase"]
        # Self-healing cursors (absent in pre-watchdog snapshots).
        pipe.walk_shards = int(meta.get("walk_shards", meta["num_shards"]))
        pipe._lr_scale = float(meta.get("lr_scale", 1.0))
        pipe._ckpt_seq = step_loaded + 1
        log.info("resumed pipeline from %s snapshot %d "
                 "(phase=%s round=%d step=%d)", root, step_loaded,
                 pipe._phase, pipe._trained_rounds, pipe.global_step)
        obs.span_event("ckpt.resume", snapshot=step_loaded,
                       phase=pipe._phase, round=pipe._trained_rounds,
                       step=pipe.global_step)
        obs.inc("ckpt.resumes")
        return pipe

    def corpus(self):
        """Materialize the ring as a host ``Corpus`` (API boundary only)."""
        from repro.core.corpus import Corpus, ring_to_numpy
        walks, lengths = ring_to_numpy(self.ring)
        stats = {k: float(v) for k, v in self._stats.items()}
        stats["d_history"] = list(self.controller.history)
        stats["mean_len"] = float(lengths.mean()) if len(lengths) else 0.0
        return Corpus(walks=walks, lengths=lengths,
                      ocn=np.asarray(self.ring.ocn, dtype=np.int64),
                      rounds=self.controller.rounds, stats=stats)

    def embeddings(self, as_numpy: bool = True):
        """Current (phi_in, phi_out) in node space, replica-averaged."""
        if self.num_shards > 1:
            phi_in = jnp.mean(self.phi_in, axis=0)
            phi_out = jnp.mean(self.phi_out, axis=0)
        else:
            phi_in, phi_out = self.phi_in[0], self.phi_out[0]
        if as_numpy:
            return np.asarray(phi_in), np.asarray(phi_out)
        return phi_in, phi_out

    # --- incremental refresh (repro.core.incremental drives this) ---------
    def corpus_slots(self):
        """(walks, roots, valid) for the resident ring slots.

        ``roots`` is the host-maintained slot→source-vertex map (updated
        at every append from the dispatch chunks, so it survives partial
        refresh rounds and ring wraps where slot arithmetic would lie);
        ``valid`` masks slots ever written. This is the corpus surface
        affected-vertex detection reads (one host pull per refresh).
        """
        walks = np.asarray(self.ring.walks)
        return walks, self._slot_root, self._slot_root >= 0

    def _rewalk_resident(self, root_mask: np.ndarray,
                         faults: FaultInjector = NULL_INJECTOR
                         ) -> Tuple[int, int]:
        """Re-walk every resident walk rooted in ``root_mask`` under its
        ORIGINAL round key and splice it into the slot its predecessor
        occupies (``ring_replace`` keeps ocn exact: − old tokens + new).

        Shared by the incremental refresh (stale roots after churn) and
        shard-loss recovery (resident roots of a dead shard) — in both
        cases vertex-keyed RNG makes the subset walks bit-identical to a
        full-batch round. Fires ``refresh_splice`` once per resident round
        BEFORE that round's splices land — an injected crash therefore dies
        with earlier rounds spliced and later rounds stale, the exact
        half-updated-ring hazard; recovery (resume from the pre-refresh
        snapshot, replay the churn, redo the refresh) must never expose
        that intermediate state. Returns (rewalk_walks, rounds_resident).
        """
        from repro.core.corpus import ring_replace_donated
        from repro.graph.delta import graph_version

        n = len(self.sources)
        slot_ids = np.arange(self.ring.capacity)
        aff_slot = (self._slot_root >= 0) & np.asarray(root_mask)[
            np.maximum(self._slot_root, 0)]
        rounds_resident = np.unique(self._slot_round[aff_slot])
        rewalk_walks = 0
        gv = int(graph_version(self.graph)) if obs.enabled() else None
        for r in rounds_resident:
            # The refresh_splice injection point fires INSIDE the span so
            # a chaos crash dumps a flight record whose faulting span
            # carries the round/graph_version (and, via log_context, the
            # shard) it died in.
            with obs.trace_span("refresh.splice", round=int(r),
                                graph_version=gv):
                faults.fire("refresh_splice", int(r))
                sel = aff_slot & (self._slot_round == r)
                roots_r = self._slot_root[sel]
                slot_of = np.full(n, -1, np.int64)
                slot_of[roots_r] = slot_ids[sel]
                for chunk, st in self._run_round(int(r), sources=roots_r,
                                                 faults=faults):
                    slots = slot_of[chunk]
                    self.ring = ring_replace_donated(
                        self.ring, jnp.asarray(slots, jnp.int32), st.path,
                        st.info.L.astype(jnp.int32))
                    for k in self._stats:
                        self._stats[k] = self._stats[k] + getattr(st, k)
                    rewalk_walks += len(chunk)
                obs.inc("refresh.rewalk_walks", int(len(roots_r)))
        return rewalk_walks, int(len(rounds_resident))

    def recover_shard_loss(self, shard_id: int, *,
                           faults: FaultInjector = NULL_INJECTOR
                           ) -> Dict[str, Any]:
        """Degraded-mode recovery for one lost walk shard: instead of
        restarting every in-flight round globally, re-walk ONLY the lost
        shard's resident roots through the subset-re-walk path under their
        original round keys. Vertex-keyed RNG makes the recovered walks
        bit-identical to what the lost shard had produced, so the ring —
        and everything downstream of it — is exactly restored, not
        approximated. Requires ``WalkSpec.rng_mode == 'vertex'``."""
        if self.spec.rng_mode != "vertex":
            raise ValueError(
                "shard-loss recovery requires WalkSpec.rng_mode='vertex'")
        n = len(self.sources)
        if self.assignment is None:
            if shard_id != 0:
                raise ValueError(
                    f"pipeline has no shard assignment (shard {shard_id})")
            mask = np.ones(n, bool)       # single shard: everything resident
        else:
            mask = np.asarray(self.assignment) == shard_id
        t0 = time.perf_counter()
        with log_context(shard=shard_id):
            rewalk, rounds = self._rewalk_resident(mask, faults)
            jax.block_until_ready(self.ring.walks)
            log.info("shard-loss recovery re-walked %d walks over %d "
                     "resident rounds", rewalk, rounds)
        return {
            "shard": int(shard_id),
            "lost_roots": int(mask.sum()),
            "rewalk_walks": int(rewalk),
            "rounds_resident": int(rounds),
            "wall_s": float(time.perf_counter() - t0),
        }

    # --- self-healing runtime (DESIGN.md §12) ------------------------------
    def _heal_divergence(self, err, faults: FaultInjector) -> None:
        """React to a watchdog verdict: roll back to the last consistent
        snapshot, back the learning rate off, quarantine the offending ring
        slots, and let ``run`` re-enter the state machine.

        The quarantine re-walks the roots whose slots fed the diverging
        chunk under their ORIGINAL round keys — on a clean ring this is a
        bit-identical no-op (vertex-keyed RNG), and if the divergence was
        seeded by corrupt walk data the regenerated slots heal it, so the
        replay cannot deterministically re-diverge on the same poison. The
        backoff handles the other deterministic-replay hazard (a genuine
        optimizer blow-up at this lr). Re-raises when no snapshot root is
        configured or ``max_rollbacks`` is exhausted — then the supervisor
        (``run_with_restarts``) is the right layer.
        """
        report = err.report
        mon = self.health
        if not self._ckpt_root or mon is None or mon.exhausted():
            raise err
        # Resolve slots → roots BEFORE restoring: the snapshot's slot map
        # may predate the rounds the diverging chunk trained on.
        roots = self._slot_root[report.slots]
        roots = np.unique(roots[roots >= 0])
        self._restore_in_place()
        self._lr_scale *= mon.cfg.lr_backoff
        quarantined = 0
        if self.spec.rng_mode == "vertex" and len(roots):
            mask = np.zeros(len(self.sources), bool)
            mask[roots] = True
            quarantined, _ = self._rewalk_resident(mask, faults)
        mon.note_rollback(restored_step=self.global_step,
                          lr_scale=self._lr_scale, quarantined=quarantined)
        obs.span_event("pipeline.heal", kind=report.kind,
                       detected_step=report.step,
                       restored_step=self.global_step,
                       lr_scale=self._lr_scale, quarantined=quarantined)
        obs.inc("pipeline.heals")
        log.warning(
            "divergence (%s) at step %d: rolled back to step %d, lr scale "
            "now %.3g, quarantined %d resident walks",
            report.kind, report.step, self.global_step, self._lr_scale,
            quarantined)

    def _restore_in_place(self) -> int:
        """Adopt the newest valid snapshot's state into THIS object (the
        in-place form of ``resume`` — run-loop wiring like the watchdog,
        checkpoint config and reconfig log survive the rollback). Returns
        the restored global step."""
        q = StreamingEmbedPipeline.resume(
            self._ckpt_root, self.policy, self.spec, self.cfg)
        keep = {k: self.__dict__[k] for k in (
            "health", "_ckpt_root", "_ckpt_every", "_ckpt_keep",
            "_faults", "_reconfigs", "_snapshot_hooks")}
        self.__dict__.update(q.__dict__)
        self.__dict__.update(keep)
        return self.global_step

    def _poll_liveness(self, liveness, faults: FaultInjector) -> None:
        """Round-boundary probe sweep: a persistently-dead walk shard is
        reassigned to the survivors instead of stalling the BSP round.
        A snapshot lands right after a reconfiguration (when checkpointing
        is on) so a later divergence rollback can never resurrect a dead
        shard's assignment."""
        if liveness is None:
            return
        for dead in liveness.poll(faults):
            name = liveness.names[dead]
            log.warning(
                "walk shard %d (launch id %d) missed %d consecutive "
                "liveness probes — reconfiguring elastically",
                dead, name, liveness.misses_to_dead)
            stats = self.elastic_reconfigure(dead, faults=faults)
            stats["launch_id"] = int(name)
            liveness.remove(dead)
            if self._ckpt_root and (self._ckpt_every or self.health):
                self.save(self._ckpt_root, faults=faults)
        for name in liveness.rejoinable():
            log.info(
                "walk shard (launch id %d) answered %d consecutive "
                "liveness probes — growing back elastically",
                name, liveness.hits_to_live)
            stats = self.elastic_rejoin(faults=faults)
            stats["launch_id"] = int(name)
            liveness.rejoin(name)
            if self._ckpt_root and (self._ckpt_every or self.health):
                self.save(self._ckpt_root, faults=faults)

    def elastic_reconfigure(self, dead_shard: int, *,
                            faults: FaultInjector = NULL_INJECTOR
                            ) -> Dict[str, Any]:
        """Continue at k-1 walk shards after a persistent shard loss.

        The dead shard's vertices re-enter the MPGP stream (highest degree
        first) and are assigned to the SURVIVING partitions by the same
        Eq. 14/15 argmax that placed them originally; the partition-local
        CSR store is rebuilt with the untouched survivors' slices reused
        (``graph.csr.reassign_partitioned_csr``); and the dead shard's
        resident walker fragments migrate by re-walking their roots under
        the original round keys — bit-identical to what the lost shard had
        produced, because vertex-keyed walks are invariant to the shard
        count (the engine's k-invariance contract). Walks rooted at
        surviving shards' vertices are never touched, so the ring — and
        the embedding — stays on the fault-free trajectory.

        The DSGL replica count (phi's leading axis) is NOT changed: it is
        a training ensemble choice, not a walk-dispatch property.
        """
        from repro.core.mpgp import compact_assignment, reassign_dead_shard
        from repro.core.shard_engine import reconfigure_partitions

        if self.assignment is None:
            raise ValueError(
                "elastic reconfiguration needs a shard assignment")
        if self.spec.rng_mode != "vertex":
            raise ValueError(
                "elastic reconfiguration requires WalkSpec.rng_mode="
                "'vertex' (walker-fragment migration re-walks under the "
                "original round keys)")
        k = self.walk_shards
        if not 0 <= dead_shard < k:
            raise ValueError(f"dead shard {dead_shard} not in [0, {k})")
        if k <= 1:
            raise ValueError("cannot reconfigure away the last walk shard")
        t0 = time.perf_counter()
        old_asn = np.asarray(self.assignment)
        orphan_mask = old_asn == dead_shard
        new_full = reassign_dead_shard(self.graph, old_asn, dead_shard,
                                       num_parts=k, tau_weight="degree")
        compacted, old_of_new = compact_assignment(new_full, dead_shard,
                                                   num_parts=k)
        eng = reconfigure_partitions(
            self.graph, old_asn, compacted, k - 1,
            old_of_new=old_of_new, key_obj=self.graph)
        self.assignment = jnp.asarray(compacted, jnp.int32)
        self.walk_shards = k - 1
        rewalk, rounds = self._rewalk_resident(orphan_mask, faults)
        jax.block_until_ready(self.ring.walks)
        stats = {
            "dead_shard": int(dead_shard),
            "walk_shards": int(self.walk_shards),
            "moved_roots": int(orphan_mask.sum()),
            "moved_frac": float(orphan_mask.mean()),
            "rewalk_walks": int(rewalk),
            "rounds_resident": int(rounds),
            "reused_shards": int(eng["reused_shards"]),
            "rebuilt_shards": int(eng["rebuilt_shards"]),
            "wall_s": float(time.perf_counter() - t0),
        }
        self._reconfigs.append(stats)
        obs.span_event("pipeline.reconfig", dead_shard=int(dead_shard),
                       walk_shards=int(self.walk_shards),
                       moved_roots=stats["moved_roots"],
                       rewalk_walks=stats["rewalk_walks"])
        obs.inc("pipeline.reconfigs")
        obs.set_gauge("walk.shards", self.walk_shards)
        with log_context(shard=dead_shard):
            log.info(
                "elastic reconfiguration: %d orphan roots -> %d survivors "
                "(%d/%d slices reused), %d resident walks migrated in "
                "%.3fs", stats["moved_roots"], self.walk_shards,
                stats["reused_shards"], k - 1, rewalk, stats["wall_s"])
        return stats

    def elastic_rejoin(self, *, faults: FaultInjector = NULL_INJECTOR
                       ) -> Dict[str, Any]:
        """Grow back k → k+1 walk shards after capacity returns.

        The returned shard re-enters the dispatch space with the HIGHEST
        id (appended — survivors' ids never move, so in-flight host state
        keyed by dispatch id stays valid). ``mpgp.rejoin_shard`` carves a
        donor region out of the overloaded survivors (BFS around the most
        loaded survivor's hub, Eq. 15 capacity bookkeeping) and the
        partition-local CSR store rebuilds with every NON-donor slice
        reused (``reassign_partitioned_csr``, split direction).

        Unlike a shard death, NO walk data is lost or invalidated:
        vertex-keyed walks are invariant to the shard count AND the
        assignment (the engine's k-invariance contract), so the ring — and
        the embedding trajectory — is untouched. Re-join is pure dispatch
        topology: the next round simply fans out over k+1 shards.
        """
        from repro.core.mpgp import rejoin_shard
        from repro.core.shard_engine import reconfigure_partitions

        if self.assignment is None:
            raise ValueError("elastic re-join needs a shard assignment")
        if self.spec.rng_mode != "vertex":
            raise ValueError(
                "elastic re-join requires WalkSpec.rng_mode='vertex' "
                "(walk dispatch must be assignment-invariant)")
        k = self.walk_shards
        t0 = time.perf_counter()
        old_asn = np.asarray(self.assignment)
        new_asn, moved = rejoin_shard(self.graph, old_asn, num_parts=k,
                                      tau_weight="degree")
        old_of_new = np.concatenate(
            [np.arange(k, dtype=np.int64), [-1]])
        eng = reconfigure_partitions(
            self.graph, old_asn, new_asn, k + 1,
            old_of_new=old_of_new, num_shards_old=k, key_obj=self.graph)
        self.assignment = jnp.asarray(new_asn, jnp.int32)
        self.walk_shards = k + 1
        stats = {
            "kind": "rejoin",
            "walk_shards": int(self.walk_shards),
            "moved_roots": int(moved.sum()),
            "moved_frac": float(moved.mean()),
            "reused_shards": int(eng["reused_shards"]),
            "rebuilt_shards": int(eng["rebuilt_shards"]),
            "wall_s": float(time.perf_counter() - t0),
        }
        self._reconfigs.append(stats)
        obs.span_event("pipeline.rejoin",
                       walk_shards=int(self.walk_shards),
                       moved_roots=stats["moved_roots"])
        obs.inc("pipeline.rejoins")
        obs.set_gauge("walk.shards", self.walk_shards)
        log.info(
            "elastic re-join: %d donor roots -> returned shard %d "
            "(%d/%d slices reused) in %.3fs", stats["moved_roots"], k,
            stats["reused_shards"], k + 1, stats["wall_s"])
        return stats

    def refresh(self, new_graph, affected_mask: np.ndarray, *,
                fine_tune_steps: Optional[int] = None,
                fine_tune_frac: float = 0.5,
                fine_tune_lr_scale: float = 0.3,
                max_extra_rounds: int = 2,
                faults: FaultInjector = NULL_INJECTOR) -> Dict[str, Any]:
        """Absorb a mutated graph: re-walk ONLY the affected roots through
        the sharded engine, splice the delta corpus into the ring, continue
        the seeded ΔD gate, and fine-tune DSGL in place.

        Per retained round r the affected roots re-walk under round r's
        ORIGINAL key; vertex-keyed RNG reproduces exactly the walks a
        from-scratch round on the mutated graph would give them, and
        ``ring_replace`` swaps them into their original round-aligned
        slots — every other slot (every walk rooted at an unaffected
        vertex) stays bit-identical. The Eq. 7 controller then continues
        from the PRIOR run's D_r history: if churn moved the
        degree/occurrence divergence by more than delta, extra
        affected-subset rounds append until it re-converges (bounded by
        ``max_extra_rounds``). Finally DSGL fine-tunes over the refreshed
        ring on a decayed mini-schedule (``fine_tune_frac`` of the
        original schedule at ``fine_tune_lr_scale``·lr), with the negative
        alias table rebuilt from the exact refreshed occurrence counts.
        """
        from repro.core.info import relative_entropy_dpq
        from repro.core.termination import WalkCountController
        from repro.graph.delta import graph_version

        if self.spec.rng_mode != "vertex":
            raise ValueError("refresh requires WalkSpec.rng_mode='vertex'")
        n = len(self.sources)
        if new_graph.num_nodes != n:
            raise ValueError(
                f"refresh cannot change the vertex set yet "
                f"({new_graph.num_nodes} != {n}); rebuild with embed_graph")
        if (getattr(self.policy, "needs_edge_cm", False)
                and new_graph.edge_cm is None):
            new_graph = new_graph.with_edge_cm()
        t0 = time.perf_counter()
        gv = int(graph_version(new_graph))
        with obs.trace_span("refresh.enter", graph_version=gv):
            faults.fire("refresh", gv)
        self.graph = new_graph
        self.degrees = np.asarray(new_graph.degrees(), dtype=np.int64)

        affected = np.nonzero(np.asarray(affected_mask))[0].astype(np.int32)
        cap = self.ring.capacity
        sup0 = int(jnp.sum(self._stats["supersteps"]))

        # --- re-walk every resident walk of an affected root; splice ------
        # each new walk into the slot its stale predecessor occupies.
        # Rounds are re-walked under their ORIGINAL round keys, so the
        # spliced walks are bit-identical to a from-scratch round on the
        # mutated graph; a root's slot within a round comes from the
        # slot_root map (a full round holds every root once, a partial
        # extra round from an earlier refresh only its subset).
        rewalk_walks, retained = self._rewalk_resident(affected_mask, faults)

        # --- seeded ΔD gate: append extra subset rounds if D moved --------
        hist = list(self.controller.history)
        gate = WalkCountController(
            delta=self.controller.delta, min_rounds=1,
            max_rounds=len(hist) + 1 + max_extra_rounds,
            window=self.controller.window, seed_history=hist)
        extra = 0
        r_next = self._rounds_walked
        while len(affected):
            ocn_host = np.asarray(self.ring.ocn)
            if not gate.update_d(relative_entropy_dpq(self.degrees,
                                                      ocn_host)):
                break
            # Appends must FIT: a wrap would overwrite retained walks of
            # UNAFFECTED roots (breaking the kept-walk bit-identity
            # contract) and _ring_append never subtracts the overwritten
            # tokens, so ocn would drift. A full ring simply stops the
            # top-up — the spliced per-round re-walks above already
            # refreshed the corpus.
            if int(self.ring.total) + len(affected) > cap:
                break
            self._append(self._run_round(r_next, sources=affected), r_next)
            rewalk_walks += len(affected)
            extra += 1
            r_next += 1
        self._rounds_walked = r_next
        self.controller = gate        # next refresh seeds from here

        # --- fine-tune DSGL over the refreshed ring -----------------------
        from repro.core.corpus import FrequencyOrder
        from repro.core.dsgl import build_alias_table

        ocn_host = np.asarray(self.ring.ocn)
        filled = self.ring.num_filled
        ft = (int(fine_tune_steps) if fine_tune_steps is not None
              else max(1, int(fine_tune_frac * self.total_steps)))
        self._ft = (self.global_step, ft,
                    float(self.cfg.lr * fine_tune_lr_scale))
        try:
            table = build_alias_table(ocn_host, self.cfg.neg_power)
            order = (FrequencyOrder.from_ocn(ocn_host)
                     if self.num_shards > 1 else None)
            done = 0
            while done < ft:
                step = min(self.steps_per_round, ft - done)
                self._train_slots(0, filled, ocn_host, step,
                                  table=table, order=order)
                done += step
        finally:
            self._ft = None
        jax.block_until_ready(self.phi_in)

        sup1 = int(jnp.sum(self._stats["supersteps"]))
        obs.inc("refresh.count")
        obs.observe("refresh.s", time.perf_counter() - t0)
        obs.set_gauge("refresh.affected", int(len(affected)))
        obs.set_gauge("refresh.graph_version", gv)
        return {
            "affected": int(len(affected)),
            "affected_frac": float(len(affected) / max(n, 1)),
            "retained_rounds": int(retained),
            "extra_rounds": int(extra),
            "rewalk_walks": int(rewalk_walks),
            "rewalk_supersteps": int(sup1 - sup0),
            "fine_tune_steps": int(ft),
            "wall_s": float(time.perf_counter() - t0),
        }

    def adopt_graph(self, new_graph) -> None:
        """Detector-only degraded refresh (DESIGN.md §12): adopt the
        mutated topology — so future walks, reconfigurations and snapshots
        see the true graph — WITHOUT re-walking or fine-tuning. The ring
        keeps its stale walks; the caller (the SLO-driven ingest ladder)
        carries the affected-root set as debt and pays it on the next
        non-degraded refresh."""
        if new_graph.num_nodes != len(self.sources):
            raise ValueError(
                f"adopt_graph cannot change the vertex set "
                f"({new_graph.num_nodes} != {len(self.sources)})")
        if (getattr(self.policy, "needs_edge_cm", False)
                and new_graph.edge_cm is None):
            new_graph = new_graph.with_edge_cm()
        self.graph = new_graph
        self.degrees = np.asarray(new_graph.degrees(), dtype=np.int64)
