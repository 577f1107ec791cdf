"""Fault-tolerant embedding serving (DESIGN.md §14).

The ingest → refresh → snapshot loop (PR 6/8) produces crash-consistent
embedding versions; this module is the read side that makes the loop a
production system: an ``EmbedServer`` on the continuous-batching
slot-pool pattern (the generic wave scheduler the LM ``runtime.server``
uses lives here as ``wave_batches``), answering

* **pair scoring** — ``(u, candidates)`` → dot-product scores, the link-
  prediction primitive (``benchmarks.common.link_prediction_auc`` uses
  the same ``(phi[u] * phi[v]).sum(-1)`` convention);
* **top-K over V** — ``(u, k)`` → the k highest-scoring vertices with
  self excluded, via a batched device product + ``lax.top_k``.

Robustness is the contract, not a feature:

* **Versioned snapshot swap** — the server holds embedding version v
  (loaded from the PR-6 crash-consistent snapshots through
  ``ckpt.read_meta`` / ``load_checkpoint``; torn steps are invisible and
  the newest VALID one is used) while ingest produces v+1, then swaps
  atomically: a wave captures its snapshot reference at formation, so
  requests batched pre-swap finish on v and post-swap batches read v+1 —
  a half-swapped read cannot be expressed.
* **Health-gated swap** — a candidate must pass ``health.SnapshotGate``
  (finite phi, version/graph_version monotonicity, norm-vs-EMA gates)
  before it is eligible; a divergent refresh never reaches readers.
* **SLO-aware degraded reads** — the serve-side degrade ladder mirrors
  the ingest ladder (DESIGN.md §12): *fresh* → *stale-ok* (keep serving
  v while the v+1 refresh is degraded / retrying / rejected; every
  response is stamped ``served_version`` / ``staleness_s``) → *shed*
  (reject at admission when the queue's predicted wait — wave-wall EMA ×
  headroom, the same predictor ``IngestDriver`` uses — blows the
  request deadline, or the queue is full).
* **Fault drills** — ``FaultInjector`` points ``swap`` (inside the swap
  window, before the commit: the active version must keep serving),
  ``serve_wave`` (the wave is re-queued — admitted queries are never
  dropped), and the ``queue_overflow`` corruption site; terminal serve
  failures (no valid snapshot and no active version) dump a flight
  record before raising.

Scoring is **order-pinned**: the d products accumulate in explicit
index order (XLA does not reassociate float adds) and product /
accumulation run as separate executables (so LLVM cannot contract
mul+add into an FMA), making device scores bit-identical to the NumPy
oracle (``oracle_scores`` / ``oracle_topk``) — the serving path is
testable against ground truth at the bit level. Top-K builds its product
d-major, (d, B, |V|), from a (d, |V|) copy of phi that each snapshot
makes at its first top-K wave, and sums it plane by plane.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Iterable, Iterator, List, \
    Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt.checkpoint import load_checkpoint
from repro.common.logging import get_logger
from repro.runtime.faults import FaultInjector, NULL_INJECTOR
from repro.runtime.health import SnapshotGate, SnapshotGateConfig

log = get_logger("repro.runtime.serve")


class ServeError(RuntimeError):
    """Terminal serve failure (no servable version exists)."""


# ---------------------------------------------------------------------------
# Slot-pool wave batching (shared with the LM server)
# ---------------------------------------------------------------------------

def wave_batches(items: Sequence, slots: int) -> Iterator[list]:
    """Yield consecutive waves of at most ``slots`` items: the refill
    order of a fixed slot pool fed from a queue (continuous batching)."""
    slots = max(int(slots), 1)
    for i in range(0, len(items), slots):
        yield list(items[i:i + slots])


# ---------------------------------------------------------------------------
# Order-pinned scoring kernels + NumPy oracle
# ---------------------------------------------------------------------------

def chain_dot(a, b):
    """Dot product along the last axis: elementwise products, then an
    EXPLICIT left-to-right chain of adds. This is the oracle-side half of
    the bit-reproducibility contract — neither numpy nor XLA reassociates
    floating-point adds, so the only divergence hazard is FMA contraction
    (LLVM fusing ``acc + a*b`` into one rounding). The device path below
    forecloses it by splitting product and accumulation into SEPARATE
    jitted executables: the accumulate kernel contains no multiply, so
    there is nothing to contract."""
    prod = a * b
    acc = prod[..., 0]
    for j in range(1, prod.shape[-1]):
        acc = acc + prod[..., j]
    return acc


@jax.jit
def _pair_products_jit(phi: jax.Array, u: jax.Array,
                       cand: jax.Array) -> jax.Array:
    """(B,) query nodes × (B, C) candidate ids → (B, C, d) products."""
    return phi[u][:, None, :] * phi[cand]


_LANES = 128          # TPU vector lanes: the minor dim of a (8, 128) tile
_FUSED_PLANES = 32    # planes of the top-K add chain per fused device pass


@jax.jit
def _phi_t_jit(phi: jax.Array) -> jax.Array:
    """(N, d) → (d, N') with N' = N rounded up to whole lanes, zero past N.
    The TPU keeps a (d, N) array with N off the lane width d-minor (it
    chooses the layout with no padding), so N is padded to make the copy,
    and every product built from it, row-major."""
    return jnp.pad(phi, ((0, -phi.shape[0] % _LANES), (0, 0))).T


@jax.jit
def _all_products_jit(phi: jax.Array, phi_t: jax.Array,
                      u: jax.Array) -> jax.Array:
    """(B,) query nodes → (d, B, N') products against every vertex of the
    d-major ``phi_t``: plane j is a contiguous (B, N') array of whole
    tiles. The query rows come from ``phi``: gathering columns of
    ``phi_t`` makes XLA:TPU transpose all of it first. The materialized
    product tensor is the price of exact reproducibility; an approximate
    fast path would use a matmul here."""
    return phi[u].T[:, :, None] * phi_t[:, None, :]


@functools.partial(jax.jit, static_argnames=("axis",))
def _accumulate_jit(prod: jax.Array, axis: int = -1) -> jax.Array:
    """Left-to-right add chain over ``axis`` — adds only, so FMA
    contraction cannot perturb the result (see ``chain_dot``). Pair
    scoring sums the last axis; top-K sums axis 0 of its d-major product,
    whose planes are whole tiles. There a barrier every ``_FUSED_PLANES``
    planes keeps each stretch of the chain one fusion that reads its
    planes in place: unbounded, XLA:TPU copies most planes out first."""
    whole_planes = axis % prod.ndim < prod.ndim - 2
    lead = (slice(None),) * (axis % prod.ndim)
    acc = prod[lead + (0,)]
    for j in range(1, prod.shape[axis]):
        acc = acc + prod[lead + (j,)]
        if whole_planes and j % _FUSED_PLANES == _FUSED_PLANES - 1:
            acc = jax.lax.optimization_barrier(acc)
    return acc


def _score_candidates(phi: jax.Array, u: jax.Array,
                      cand: jax.Array) -> jax.Array:
    """(B,) query nodes × (B, C) candidate ids → (B, C) scores."""
    return _accumulate_jit(_pair_products_jit(phi, u, cand))


@functools.partial(jax.jit, static_argnames=("k", "n"))
def _topk_from_scores_jit(scores: jax.Array, u: jax.Array, k: int, n: int):
    """(B, N') scores → (values, ids) of the k best of the first n
    vertices, self excluded."""
    scores = scores[:, :n].at[jnp.arange(u.shape[0]), u].set(-jnp.inf)
    return jax.lax.top_k(scores, k)


def _topk(snap: EmbedSnapshot, u: jax.Array, k: int):
    """(B,) query nodes → (values, ids) of the snapshot's k best vertices,
    scored on its d-major copy of phi."""
    scores = _accumulate_jit(_all_products_jit(snap.phi, snap.phi_t(), u),
                             axis=0)
    return _topk_from_scores_jit(scores, u, k, n=snap.phi.shape[0])


def oracle_scores(phi: np.ndarray, u: int,
                  candidates: np.ndarray) -> np.ndarray:
    """NumPy reference for pair scoring — same chain, same order."""
    phi = np.asarray(phi, np.float32)
    cand = np.asarray(candidates)
    return chain_dot(phi[int(u)][None, :], phi[cand])


def oracle_topk(phi: np.ndarray, u: int, k: int):
    """NumPy reference for top-K: (values, ids), self excluded, ties
    broken toward the lower id (matching ``lax.top_k``)."""
    phi = np.asarray(phi, np.float32)
    query = phi[int(u)][None, :]
    # Row blocks keep each block's column adds in cache; every score is
    # still the same chain, so the result does not depend on the block.
    scores = np.concatenate([chain_dot(query, phi[i:i + 8192])
                             for i in range(0, len(phi), 8192)])
    scores[int(u)] = -np.inf
    ids = np.argsort(-scores, kind="stable")[:k]
    return scores[ids], ids


# ---------------------------------------------------------------------------
# Request / response / snapshot types
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Query:
    """One admitted read: pair scoring (``candidates``) or top-K (``k``)."""

    qid: int
    u: int
    candidates: Optional[np.ndarray] = None
    k: int = 0
    deadline_s: Optional[float] = None
    submit_t: float = 0.0


@dataclasses.dataclass
class Response:
    """Every response is stamped with the version that produced it and
    how stale that version is — the degraded-read contract: a reader can
    always tell fresh from stale-ok."""

    qid: int
    u: int
    ids: np.ndarray             # candidate ids (echoed) or top-K ids
    scores: np.ndarray
    served_version: int
    served_graph_version: int
    staleness_s: float
    freshness: str              # "fresh" | "stale"
    latency_s: float
    wave: int                   # id of the wave that answered it


@dataclasses.dataclass
class EmbedSnapshot:
    """One immutable servable version. ``phi`` lives on device; waves
    capture the whole object by reference, so a swap can never tear a
    wave's read."""

    phi: jax.Array              # (N, d) node embeddings
    version: int                # checkpoint step (snapshot sequence)
    graph_version: int
    global_step: int
    created_t: float            # server clock at swap commit
    _phi_t: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False, compare=False)
    _phi_t_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def phi_t(self) -> jax.Array:
        """The d-major copy of ``phi`` that top-K scores on (see
        ``_phi_t_jit``), made at the first top-K wave that reads this
        version: a server that answers only pair reads holds none."""
        with self._phi_t_lock:
            if self._phi_t is None:
                self._phi_t = _phi_t_jit(self.phi)
                obs.inc("serve.topk_phi_t_builds")
            return self._phi_t


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 32       # slot-pool width per wave
    max_queue: int = 1024       # admission queue bound (overflow → shed)
    default_deadline_s: Optional[float] = None   # per-request unless set
    headroom: float = 1.5       # predicted wait = waves × EMA × headroom
    ema_beta: float = 0.5       # wave-wall EMA decay (as IngestDriver)
    latency_window: int = 256   # response-latency percentile history


_UNSET = object()


class EmbedServer:
    """Versioned, SLO-aware embedding read path over one slot pool.

    Single writer (the ingest/refresh lifecycle offering snapshots),
    many readers (``submit`` + ``tick``). The active-version pointer,
    the queue, and the ladder state share one lock; scoring itself runs
    outside it on the wave's captured snapshot.
    """

    def __init__(self, cfg: ServeConfig = ServeConfig(), *,
                 gate: Optional[SnapshotGate] = None,
                 faults: FaultInjector = NULL_INJECTOR,
                 clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.gate = gate or SnapshotGate(SnapshotGateConfig())
        self.faults = faults
        self.clock = clock
        self._lock = threading.Lock()
        self._queue: Deque[Query] = deque()
        self._active: Optional[EmbedSnapshot] = None
        self._next_qid = 0
        self._next_wave = 0
        self._wave_ema: Optional[float] = None
        self._newer_pending = False     # a newer candidate exists but was
                                        # rejected (torn / unhealthy)
        self.refresh_state = "ok"       # "ok" | "degraded" | "failed"
        self.responses: Dict[int, Response] = {}
        # -- accounting ------------------------------------------------------
        self.admitted = 0
        self.served = 0
        self.shed: Dict[str, int] = {}
        self.swaps = 0
        self.rejected_candidates = 0
        self.wave_faults = 0
        self.served_by_version: Dict[int, int] = {}
        self.served_by_freshness = {"fresh": 0, "stale": 0}
        self._latency = obs.Histogram(window=max(cfg.latency_window, 1))
        obs.REGISTRY.attach("serve.latency_s", self._latency)

    # -- versioned snapshot swap --------------------------------------------
    def offer_snapshot(self, root: str, step: Optional[int] = None) -> bool:
        """Load, health-gate, and (if admitted) atomically swap in the
        newest valid checkpoint under ``root``. Returns True on swap.

        Torn/corrupt steps are invisible to the loader (it falls back to
        the newest valid one); a fallback that is not newer than the
        active version is a no-op, not a regression. A candidate the gate
        rejects leaves the active version serving and marks the ladder
        stale (a newer version exists but is unhealthy). Having NO active
        version and no servable candidate is terminal: flight-record dump
        + raise — there is nothing to degrade to.
        """
        with obs.trace_span("serve.offer", root=str(root)):
            try:
                loaded, arrays, meta = load_checkpoint(
                    root, step, only=("phi_in",))
            except (FileNotFoundError, OSError, ValueError) as e:
                obs.inc("serve.offer.unreadable")
                if self._active is None:
                    obs.dump_flight_record("serve_no_snapshot",
                                           root=str(root), error=str(e))
                    raise ServeError(
                        f"no servable snapshot under {root}: {e}") from e
                log.warning("snapshot offer unreadable (%s); keeping "
                            "version %d", e, self._active.version)
                return False

            if self._active is not None and loaded <= self._active.version:
                # Re-offer of the active (or an older fallback after a
                # torn newer step): nothing to do, nothing unhealthy.
                obs.inc("serve.offer.not_newer")
                return False

            phi = np.asarray(arrays["phi_in"], np.float32)
            if phi.ndim == 3:           # (S, N, d) replicas → node space
                phi = phi[0] if phi.shape[0] == 1 else phi.mean(axis=0)
            gv = int(meta.get("graph_version", 0))
            # The swap window: a crash here (drill point "swap") leaves
            # the previous version serving AND the gate's monotonic
            # record untouched, so the same step can be re-offered —
            # the gate must only remember snapshots that COMMITTED.
            self.faults.fire("swap", note=loaded)
            ok, reason = self.gate.admit(phi, version=loaded,
                                         graph_version=gv)
            if not ok:
                self.rejected_candidates += 1
                if self._active is None:
                    obs.dump_flight_record("serve_candidate_rejected",
                                           root=str(root), version=loaded,
                                           gate_reason=reason)
                    raise ServeError(
                        f"candidate snapshot {loaded} rejected ({reason}) "
                        "with no active version to fall back to")
                with self._lock:
                    self._newer_pending = True
                log.warning("candidate snapshot %d rejected (%s); serving "
                            "version %d stale", loaded, reason,
                            self._active.version)
                return False

            snap = EmbedSnapshot(
                phi=jnp.asarray(phi), version=int(loaded),
                graph_version=gv,
                global_step=int(meta.get("global_step", 0)),
                created_t=self.clock())
            # The commit is a single pointer store under the lock.
            with self._lock:
                self._active = snap
                self._newer_pending = False
            self.swaps += 1
            obs.inc("serve.swaps")
            obs.set_gauge("serve.active_version", loaded)
            obs.set_gauge("serve.active_graph_version", gv)
            obs.span_event("serve.swap", version=loaded, graph_version=gv)
            return True

    def note_refresh(self, state: str) -> None:
        """Ingest-side refresh status feed: "ok" | "degraded" | "failed".
        Anything but "ok" moves responses to the stale-ok rung until the
        next successful swap."""
        assert state in ("ok", "degraded", "failed"), state
        with self._lock:
            self.refresh_state = state
        obs.inc(f"serve.refresh.{state}")

    def active_version(self) -> Optional[int]:
        with self._lock:
            return None if self._active is None else self._active.version

    def active_phi(self) -> Optional[np.ndarray]:
        with self._lock:
            snap = self._active
        return None if snap is None else np.asarray(snap.phi)

    # -- admission ----------------------------------------------------------
    def submit(self, u: int, candidates: Optional[Iterable[int]] = None, *,
               k: Optional[int] = None, deadline_s: Any = _UNSET
               ) -> Optional[int]:
        """Admit one query (returns its qid) or shed it (returns None).

        Shedding happens only at admission — an admitted query is always
        answered (fresh or stale): no version at all, a full queue (or
        the ``queue_overflow`` drill), or a predicted wait that blows the
        deadline all reject at the door with backpressure.
        """
        if deadline_s is _UNSET:
            deadline_s = self.cfg.default_deadline_s
        now = self.clock()
        with self._lock:
            if self._active is None:
                return self._shed("no_version")
            if self.faults.inject("queue_overflow") \
                    or len(self._queue) >= self.cfg.max_queue:
                return self._shed("overflow")
            if deadline_s is not None and self._wave_ema is not None:
                waves_ahead = len(self._queue) // self.cfg.batch_slots + 1
                predicted = waves_ahead * self._wave_ema \
                    * self.cfg.headroom
                if predicted > deadline_s:
                    return self._shed("deadline")
            qid = self._next_qid
            self._next_qid += 1
            q = Query(qid=qid, u=int(u),
                      candidates=(None if candidates is None
                                  else np.asarray(candidates, np.int32)),
                      k=int(k or 0), deadline_s=deadline_s, submit_t=now)
            self._queue.append(q)
            self.admitted += 1
        obs.inc("serve.admitted")
        return qid

    def _shed(self, reason: str) -> None:
        """(lock held) Count one shed admission."""
        self.shed[reason] = self.shed.get(reason, 0) + 1
        obs.inc(f"serve.shed.{reason}")
        return None

    # -- the serving loop ---------------------------------------------------
    def tick(self) -> List[Response]:
        """Score one wave from the queue on the snapshot captured at wave
        formation. On a wave fault the wave is re-queued at the front and
        the failure propagates — admitted queries survive the crash.

        The wave runs inside the phase ``serve.tick``, tiled by its
        children ``serve.form``, ``serve.group``, ``serve.dispatch``,
        ``serve.fetch`` and ``serve.respond`` (DESIGN.md §13)."""
        if not self._queue:
            return []
        with obs.phase("serve.tick"):
            with obs.phase("serve.form"):
                with self._lock:
                    if not self._queue:
                        return []
                    take = min(len(self._queue),
                               max(self.cfg.batch_slots, 1))
                    wave = [self._queue.popleft() for _ in range(take)]
                    snap = self._active
                    freshness = self._freshness_locked()
                    wave_id = self._next_wave
                    self._next_wave += 1
                t0 = self.clock()
                if obs.enabled():
                    obs.REGISTRY.histogram("serve.queue_wait_s").observe_many(
                        [t0 - q.submit_t for q in wave])
            try:
                self.faults.fire("serve_wave", note=len(wave))
                scored = self._score_wave(wave, snap)
            except Exception:
                with self._lock:
                    self._queue.extendleft(reversed(wave))
                self.wave_faults += 1
                obs.inc("serve.wave_faults")
                raise
            now = self.clock()
            with obs.phase("serve.respond"):
                return self._respond(wave, wave_id, scored, snap,
                                     freshness, now - t0, now)

    def _respond(self, wave: List[Query], wave_id: int, scored: list,
                 snap: EmbedSnapshot, freshness: str, wall: float,
                 now: float) -> List[Response]:
        """Slice each read's answer out of its group's host arrays, stamp
        the responses and account for them."""
        with self._lock:
            b = self.cfg.ema_beta
            self._wave_ema = (wall if self._wave_ema is None
                              else b * self._wave_ema + (1 - b) * wall)
        answers: Dict[int, tuple] = {}
        for group, ids, scores in scored:
            for i, q in enumerate(group):
                answers[q.qid] = ((ids[i], scores[i]) if ids is not None
                                  else (q.candidates,
                                        scores[i, :len(q.candidates)]))
        staleness = max(now - snap.created_t, 0.0)
        out = []
        for q in wave:
            ids, scores = answers[q.qid]
            resp = Response(
                qid=q.qid, u=q.u, ids=ids, scores=scores,
                served_version=snap.version,
                served_graph_version=snap.graph_version,
                staleness_s=staleness, freshness=freshness,
                latency_s=now - q.submit_t, wave=wave_id)
            self.responses[q.qid] = resp
            out.append(resp)
            self._latency.observe(resp.latency_s)
        self.served += len(out)
        self.served_by_version[snap.version] = \
            self.served_by_version.get(snap.version, 0) + len(out)
        self.served_by_freshness[freshness] += len(out)
        obs.inc("serve.responses", len(out))
        obs.set_gauge("serve.staleness_s", staleness)
        return out

    def _freshness_locked(self) -> str:
        return ("fresh" if self.refresh_state == "ok"
                and not self._newer_pending else "stale")

    def _score_wave(self, wave: List[Query], snap: EmbedSnapshot) -> list:
        """Batched device scoring of one wave: ``[(group, ids, scores)]``
        in host arrays, ``ids`` None for a pair group (its reads' own
        candidates). Top-K queries group by k, pair queries by a padded
        candidate bucket (powers of two, to bound recompiles); padding
        never leaks — ``_respond`` trims each read's slice. Every group is
        dispatched before the first is fetched."""
        with obs.phase("serve.group"):
            topk_groups: Dict[int, List[Query]] = {}
            cand_groups: Dict[int, List[Query]] = {}
            for q in wave:
                if q.candidates is None:
                    topk_groups.setdefault(q.k, []).append(q)
                else:
                    width = max(1, 1 << (len(q.candidates) - 1)
                                .bit_length()) if len(q.candidates) else 1
                    cand_groups.setdefault(width, []).append(q)
            padded = []
            for width, group in cand_groups.items():
                cand = np.zeros((len(group), width), np.int32)
                for i, q in enumerate(group):
                    cand[i, :len(q.candidates)] = q.candidates
                padded.append((group, cand))
        with obs.phase("serve.dispatch"):
            pending = []
            for k, group in topk_groups.items():
                u = jnp.asarray([q.u for q in group], jnp.int32)
                vals, ids = _topk(snap, u, k)
                pending.append((group, ids, vals))
            for group, cand in padded:
                u = jnp.asarray([q.u for q in group], jnp.int32)
                pending.append((group, None, _score_candidates(
                    snap.phi, u, jnp.asarray(cand))))
        with obs.phase("serve.fetch"):
            return [(group, None if ids is None else np.asarray(ids),
                     np.asarray(scores)) for group, ids, scores in pending]

    def drain(self) -> List[Response]:
        """Tick until the queue is empty; responses in completion order."""
        out: List[Response] = []
        while True:
            batch = self.tick()
            if not batch:
                return out
            out.extend(batch)

    def serve(self, queries: List[Dict[str, Any]]) -> List[Optional[Response]]:
        """Convenience: submit a list of ``{"u", "candidates"|"k", ...}``
        dicts, drain, and return responses aligned to the input order
        (``None`` where admission shed the query)."""
        qids = [self.submit(spec["u"], spec.get("candidates"),
                            k=spec.get("k"),
                            deadline_s=spec.get("deadline_s", _UNSET))
                for spec in queries]
        self.drain()
        return [None if qid is None else self.responses.get(qid)
                for qid in qids]

    # -- accounting ---------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            depth = len(self._queue)
            active = self._active
            freshness = self._freshness_locked()
        shed_total = sum(self.shed.values())
        return {
            "admitted": self.admitted,
            "served": self.served,
            "shed": dict(self.shed),
            "shed_total": shed_total,
            "offered_total": self.admitted + shed_total,
            "availability": self.served / max(self.admitted, 1),
            "swaps": self.swaps,
            "rejected_candidates": self.rejected_candidates,
            "wave_faults": self.wave_faults,
            "queue_depth": depth,
            "active_version": None if active is None else active.version,
            "refresh_state": self.refresh_state,
            "freshness": freshness,
            "served_by_version": dict(self.served_by_version),
            "served_by_freshness": dict(self.served_by_freshness),
            "latency_p50_s": self._latency.percentile(50),
            "latency_p99_s": self._latency.percentile(99),
        }
