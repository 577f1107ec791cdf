"""Partition-sharded BSP walk engine (paper §3: walker-centric + InCoM).

Walkers live on the shard that owns their CURRENT node per the MPGP
``assignment``; one superstep is:

  phase A (at owner(cur))   candidate draw + walking-backtracking
                            acceptance (``walker.propose``);
  exchange                  walkers whose accepted node belongs to another
                            shard pack the paper's constant-size InCoM
                            message and hand off via a collective;
  phase B (at owner(cand))  n(v) from the LOCAL path fragment, Theorem 1 /
                            Eq. 13 info update, path append, Eq. 5
                            termination (``walker.absorb``).

Path storage follows the paper's ownership argument: node v's visits are
always appended on owner(v)'s fragment, so n(v) is a local count and the
walk itself never has to travel — only the 10-field / 80-byte message does
(Example 1). The final corpus path is the elementwise union of the shard
fragments (every position is written by exactly one shard). The fullpath
(HuGE-D) baseline instead carries the whole walk in its message: 24 + 8L
bytes, measured from the actual routed path payload.

Two engines realize the per-shard program (DESIGN.md §9):

* **partition-local** (the scaling engine; default on a real mesh): each
  shard program indexes ONLY its ``graph.csr.build_partitioned_csr``
  slice — a local-row CSR of ~|V|/k nodes and ~|E|/k arcs with
  edge-aligned halo metadata (neighbor owner + degree), so ``owner[]``
  lookups for candidates never touch a global O(|E|) structure. Walker
  lanes are COMPACTED into a per-shard slot pool sized by the MPGP
  balance bound (``pool_factor``·B/k, grown to the observed occupancy on
  overflow), so phase-A/phase-B work scales with walkers-per-shard, not
  with the global batch. The exchange moves only migrant records —
  ``lax.all_to_all`` destination buckets with an overflow spill loop on
  the mesh, gather-compacted broadcasts on the stacked path — instead of
  the former dense all-lane psum.
* **replicated** (reference + single-device fast path): every shard reads
  the replicated CSR and carries all B lanes; the exchange is the dense
  ``psum_union``. Second-order policies that read N(prev) (node2vec)
  always route here, the stacked emulation defaults here (on one device
  the k per-shard programs serialize, so partition-locality saves no
  memory and the dense form wins wall-clock), and tests use it as the
  ground truth the partition-local engine must match walk-for-walk.

Message layout: exactly ``incom.MSG_FIELDS`` (10 fields). The walker's step
count is globally known (BSP superstep index), so the ``steps`` slot
carries the sender's pre-step node instead — the predecessor that
second-order policies (node2vec) need on arrival — keeping the hand-off at
the paper's 80 bytes (DESIGN.md §9). ``reg_window`` mode appends the K-entry
H ring (80 + 8K bytes), matching ``incom.windowed_r_squared``'s cost note.

Both engines execute the SAME per-shard program two ways:

* ``vmap(..., axis_name="shards")`` — stacked emulation: k logical shards
  as a leading array axis on one device. Always available, used by tests
  for shard-count invariance.
* ``shard_map`` over a k-device mesh — the SPMD form with real collectives
  (``make_walk_mesh``); the partition-local engine places only the owning
  CSR slice on each device. Bit-identical by construction: per-lane RNG
  (``walker.step_uniforms``) and per-lane math do not depend on layout.

``msg_count``/``msg_bytes`` are derived from the packed message tensors
the exchange moves: per hand-off, the FIELD COUNT of the packed payload x
the paper's 8 B/field accounting (Example 1) — so a packing regression
(an extra field, a whole-batch ship) moves the number away from
``msg_bytes_analytic``, which carries the independent closed form.
Physical wire bytes differ: payloads are f32/i32 (4 B/field); the hand-off
COUNT and field inventory are what is measured, the 8 B/field model prices
them (DESIGN.md §9).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import incom
from repro import obs
from repro.core import walker as wk
from repro.core.transition import Policy
from repro.graph.csr import CSRGraph, PartitionedCSR, ShardCSR, \
    build_partitioned_csr

AXIS = "shards"   # the walk-shard mesh / vmap axis name


def make_walk_mesh(num_shards: int) -> Mesh:
    """A ("shards",)-mesh over ``num_shards`` local devices. Raises when the
    host has fewer; the stacked emulation (the same program under vmap) is
    what ``run_walk_sharded`` runs when no mesh is passed."""
    from repro.dist.collectives import local_mesh
    return local_mesh(num_shards, AXIS)


# ---------------------------------------------------------------------------
# Replicated reference program (full-width lanes, dense psum exchange)
# ---------------------------------------------------------------------------


def _shard_program_replicated(
    graph: CSRGraph,
    owner: jax.Array,        # (|V|,) int32 partition id per node (replicated)
    sources: jax.Array,      # (B,) int32 (replicated; lanes are global slots)
    root_key: jax.Array,
    policy: Policy,
    spec: wk.WalkSpec,
):
    """Full walk loop for ONE shard; collectives over axis ``AXIS``."""
    b = sources.shape[0]
    ids = jnp.arange(b, dtype=jnp.int32)
    sid = lax.axis_index(AXIS)
    fullpath = spec.info_mode == "fullpath"
    h_len = spec.max_len if fullpath else 1
    k_ring = max(spec.reg_window, 1)
    cap = spec.supersteps_cap()

    ufn = wk.make_uniform_fn(spec, sources)
    resident0 = owner[sources] == sid
    # Fragment init: the source node's first visit is recorded at ITS owner.
    path0 = jnp.full((b, spec.max_len), -1, jnp.int32)
    path0 = path0.at[:, 0].set(jnp.where(resident0, sources, -1))

    st0 = dict(
        cur=sources,
        prev=sources,
        resident=resident0,
        active=jnp.ones((b,), bool),
        info=incom.InfoState.init(b),
        path=path0,
        h=jnp.zeros((b, h_len), jnp.float32),
        ring=jnp.zeros((b, k_ring), jnp.float32),
        t=jnp.zeros((), jnp.int32),
        accepts=jnp.zeros((), jnp.int32),
        rejects=jnp.zeros((), jnp.int32),
        msg_count=jnp.zeros((), jnp.int32),
        msg_bytes=jnp.zeros((), jnp.float32),
        msg_bytes_analytic=jnp.zeros((), jnp.float32),
    )

    def cond(st):
        live = jnp.sum((st["resident"] & st["active"]).astype(jnp.int32))
        return (lax.psum(live, AXIS) > 0) & (st["t"] < cap)

    def body(st):
        u1, u2 = ufn(root_key, st["t"])
        cand, _, accept_raw, has_nbrs = wk.propose(
            graph, policy, st["cur"], st["prev"], u1, u2)
        live = st["resident"] & st["active"]
        accept = live & accept_raw
        dead_end = live & ~has_nbrs
        mig = accept & (owner[cand] != sid)
        stay = accept & ~mig

        path = st["path"]
        if fullpath:
            # The HuGE-D message carries the walk INCLUDING the accepted
            # node (24 + 8*l_new bytes), so append at the origin; phase B's
            # re-append at the same slot is idempotent.
            idx = jnp.clip(st["info"].L.astype(jnp.int32), 0, spec.max_len - 1)
            path = jnp.where(accept[:, None], path.at[ids, idx].set(cand), path)

        # ---- pack + hand off (the measured exchange) ------------------------
        from repro.dist.collectives import psum_union

        info = st["info"]
        mig_i = mig.astype(jnp.int32)
        msg_i = jnp.stack([ids, st["cur"], cand], axis=1)
        msg_f = jnp.stack(
            [info.H, info.L, info.EH, info.EL, info.EHL, info.EH2, info.EL2],
            axis=1)
        payload = {"i": msg_i, "f": msg_f}
        if spec.reg_window:
            payload["ring"] = st["ring"]
        if fullpath:
            payload.update({"path": path, "h": st["h"]})
        arrivals = psum_union(payload, mig, AXIS)     # exact: <=1 sender/lane
        arr_i, arr_f = arrivals["i"], arrivals["f"]
        arr_ring = arrivals.get("ring", st["ring"])
        arrived = lax.psum(mig_i, AXIS) > 0           # (B,) any shard sent
        if fullpath:
            arr_path, arr_h = arrivals["path"], arrivals["h"]
        # Fields the hand-off actually ships, derived from the packed
        # tensors (NOT from the Example-1 closed form — packing an extra
        # field would move measured away from analytic and fail the tests).
        # In fullpath mode the walk itself is the payload: the 3 id fields
        # + one entry per shipped path position; the 7-stat ride-along is
        # excluded per the paper's 24+8L accounting (module docstring).
        shipped_fields = msg_i.shape[1] + msg_f.shape[1] + (
            arrivals["ring"].shape[1] if "ring" in payload else 0)

        incoming = arrived & (owner[arr_i[:, 2]] == sid)
        proc = stay | incoming

        # ---- merge arrivals into local lane state --------------------------
        sel = lambda a, b_: jnp.where(incoming, a, b_)
        cand_b = sel(arr_i[:, 2], cand)
        sender_cur = sel(arr_i[:, 1], st["cur"])      # walker's pre-step node
        info_b = incom.InfoState(
            H=sel(arr_f[:, 0], info.H), L=sel(arr_f[:, 1], info.L),
            EH=sel(arr_f[:, 2], info.EH), EL=sel(arr_f[:, 3], info.EL),
            EHL=sel(arr_f[:, 4], info.EHL), EH2=sel(arr_f[:, 5], info.EH2),
            EL2=sel(arr_f[:, 6], info.EL2))
        ring_b = jnp.where(incoming[:, None], arr_ring, st["ring"])
        if fullpath:
            path_b = jnp.where(incoming[:, None], arr_path, path)
            h_b = jnp.where(incoming[:, None], arr_h, st["h"])
        else:
            path_b, h_b = path, st["h"]

        info2, path2, h2, ring2, done_now = wk.absorb(
            spec, info_b, path_b, h_b, ring_b, cand_b, proc)

        # ---- residence / activity -------------------------------------------
        resident2 = (st["resident"] & ~mig) | incoming
        cur2 = jnp.where(proc, cand_b, st["cur"])
        prev2 = jnp.where(proc, sender_cur, st["prev"])
        active2 = jnp.where(proc, ~done_now,
                            jnp.where(dead_end, False, st["active"]))

        # ---- measured + analytic traffic ------------------------------------
        n_out = jnp.sum(mig_i)
        if fullpath:
            shipped = jnp.sum(((path >= 0) & mig[:, None]).astype(jnp.int32))
            add_meas = (8.0 * msg_i.shape[1]) * n_out + 8.0 * shipped
            add_an = jnp.sum(jnp.where(
                mig, incom.fullpath_msg_bytes(info.L + 1.0), 0.0))
        else:
            add_meas = jnp.float32(8.0 * shipped_fields) * n_out
            add_an = jnp.float32(incom.MSG_BYTES + 8 * (spec.reg_window or 0)
                                 ) * n_out

        return dict(
            cur=cur2, prev=prev2, resident=resident2, active=active2,
            info=info2, path=path2, h=h2, ring=ring2,
            t=st["t"] + 1,
            accepts=st["accepts"] + jnp.sum(accept).astype(jnp.int32),
            rejects=st["rejects"]
            + jnp.sum(live & has_nbrs & ~accept_raw).astype(jnp.int32),
            msg_count=st["msg_count"] + n_out,
            msg_bytes=st["msg_bytes"] + add_meas,
            msg_bytes_analytic=st["msg_bytes_analytic"] + add_an,
        )

    return lax.while_loop(cond, body, st0)


# ---------------------------------------------------------------------------
# Partition-local compacted program (slot pool + packed sparse exchange)
# ---------------------------------------------------------------------------


def _info_select(take, arrived: incom.InfoState, old: incom.InfoState,
                 ) -> incom.InfoState:
    return jax.tree_util.tree_map(
        lambda a, o: jnp.where(take, a, o), arrived, old)


def _shard_program_local(
    shard: ShardCSR,         # THIS shard's slice (leading k-axis mapped away)
    local_of: jax.Array,     # (|V|,) int32 global node -> local row at owner
    owner: jax.Array,        # (|V|,) int32 partition id per node (replicated)
    sources: jax.Array,      # (B,) int32 global lane -> source node
    root_key: jax.Array,
    policy: Policy,
    spec: wk.WalkSpec,
    num_shards: int,
    pool: int,               # slot-pool size P (MPGP bound, grown on overflow)
    cap: int,                # packed-exchange records/source/round (0 = P)
    compact_every: int,      # supersteps unrolled per flush/repack block
    transport: str,          # "pool" | "gather" | "a2a"
):
    """Compacted walk loop for ONE shard over its partition-local slice.

    Lane state lives in a P-slot pool (P ~ pool_factor·B/k): slot i holds
    the GLOBAL lane id in ``lane[i]`` (-1 = free) plus that walker's
    cur/prev/info/ring and its owner-local path row. Phase A indexes only
    the local CSR slice; migrants ship compacted; arrivals claim free
    slots in deterministic (source shard, record position) order. Per-lane
    values never depend on slot position, which is what keeps walks
    bit-identical to the replicated reference at every k and under every
    transport/execution.

    The hot loop is engineered for XLA-CPU emulation as much as for real
    meshes: ZERO data-dependent scatters and ZERO nested control flow per
    superstep (batched scatters lower to serial per-entry loops, and
    inner while/cond blocks force per-iteration buffer copies — together
    they measured ~10x the actual compute). Concretely:

    * appends are one-hot selects; packing/placement are
      cumsum + compare + gather;
    * the "pool" transport all_gathers the P-wide lane payload masked by
      the migrant flags — one round always suffices, so there is no spill
      loop to execute; the packed "gather" (stacked default — its spill
      loop constant-folds away when migration is impossible and self-skips
      on migrant-free supersteps) and "a2a" (mesh default, where wire
      volume is real) transports keep the cap + spill-round while_loop;
    * terminated walkers tombstone in place, out-migrated walkers leave
      fragment GHOSTS (their owner-local path rows, resumed if the walker
      returns), and one unconditional flush per ``compact_every``-unrolled
      superstep block retires both through the engine's single batched
      scatter (the lane->slot inverse index).

    A walker that finds no free slot is counted in ``overflow`` and the
    driver re-runs with a doubled pool (P = B can never overflow: a lane
    occupies at most one slot per shard).
    """
    b = sources.shape[0]
    k = num_shards
    sid = lax.axis_index(AXIS)
    fullpath = spec.info_mode == "fullpath"
    h_len = spec.max_len if fullpath else 1
    k_ring = max(spec.reg_window, 1)
    step_cap = spec.supersteps_cap()
    p = pool
    max_nodes = shard.indptr.shape[0] - 1
    max_edges = shard.indices.shape[0]
    pids = jnp.arange(p, dtype=jnp.int32)
    flat = transport == "pool"
    r_cap = p if flat else cap
    n_rec = k * r_cap                     # records visible per round
    unroll = max(compact_every, 1)

    from repro.dist.collectives import (
        packed_all_gather, packed_all_to_all, rank_search, take_ranked)

    ufn = wk.make_uniform_fn(spec, sources)

    # ---- pool init: resident source lanes claim slots in lane order -------
    resident0 = owner[sources] == sid
    lane0_all, valid0 = take_ranked(
        jnp.arange(b, dtype=jnp.int32), resident0, p)
    lane0 = jnp.where(valid0, lane0_all, -1)
    occ0 = lane0 >= 0
    cur0 = jnp.where(occ0, sources[jnp.maximum(lane0, 0)], 0)
    overflow0 = jnp.maximum(
        jnp.sum(resident0.astype(jnp.int32)) - jnp.int32(p), 0)

    st0 = dict(
        lane=lane0,
        alive=occ0,
        term=jnp.zeros((p,), bool),
        cur=cur0,
        prev=cur0,
        info=incom.InfoState.init(p),
        ring=jnp.zeros((p, k_ring), jnp.float32),
        h=jnp.zeros((p, h_len), jnp.float32),
        # Pool-resident walk rows: the owner-local path FRAGMENT (incom /
        # fixed — appended in place, never shipped) or the travelling full
        # path (fullpath). One-hot selects keep every append vectorized.
        prow=jnp.full((p, spec.max_len), -1, jnp.int32
                      ).at[:, 0].set(jnp.where(occ0, cur0, -1)),
        # Lane-indexed fragment store: rows retire here from the pool at
        # flush ticks; the final corpus path is the max-union over shards.
        frag=jnp.full((b, spec.max_len), -1, jnp.int32),
        fin_cur=jnp.zeros((b,), jnp.int32),
        fin_prev=jnp.zeros((b,), jnp.int32),
        fin_info=incom.InfoState.init(b),
        fin_ring=jnp.zeros((b, k_ring), jnp.float32),
        fin_h=jnp.zeros((b, h_len), jnp.float32),
        fin_valid=jnp.zeros((b,), bool),
        fin_active=jnp.zeros((b,), bool),
        t=jnp.zeros((), jnp.int32),
        accepts=jnp.zeros((), jnp.int32),
        rejects=jnp.zeros((), jnp.int32),
        msg_count=jnp.zeros((), jnp.int32),
        msg_bytes=jnp.zeros((), jnp.float32),
        msg_bytes_analytic=jnp.zeros((), jnp.float32),
        overflow=overflow0,
        peak_occ=jnp.sum(occ0.astype(jnp.int32)),
    )
    if fullpath:
        st0["fin_path"] = jnp.full((b, spec.max_len), -1, jnp.int32)

    def flush_into(st, mask, active_mask):
        """Retire ``mask`` slots into the lane-indexed buffers (fragment
        store + fin state). ONE (P,)-entry scatter builds the lane->slot
        inverse index; every field then moves by (B,)-gather + select —
        the only batched scatter in the engine, paid once per unrolled
        block, never per superstep."""
        lane = st["lane"]
        slot_of = jnp.full((b,), p, jnp.int32).at[
            jnp.where(mask, lane, b)].set(pids, mode="drop")
        mo = slot_of < p                                  # (B,) lane flushed
        src = jnp.minimum(slot_of, p - 1)
        take = lambda x: x[src]
        mt = mo & take(st["term"])
        ma = mo & take(active_mask)
        mfin = mt | ma
        st = dict(st)
        if not fullpath:
            st["frag"] = jnp.where(mo[:, None], st["prow"][src], st["frag"])
        st["fin_cur"] = jnp.where(mfin, take(st["cur"]), st["fin_cur"])
        st["fin_prev"] = jnp.where(mfin, take(st["prev"]), st["fin_prev"])
        st["fin_info"] = jax.tree_util.tree_map(
            lambda xp, xf: jnp.where(mfin, xp[src], xf),
            st["info"], st["fin_info"])
        st["fin_ring"] = jnp.where(mfin[:, None], st["ring"][src],
                                   st["fin_ring"])
        st["fin_h"] = jnp.where(mfin[:, None], st["h"][src], st["fin_h"])
        st["fin_valid"] = st["fin_valid"] | mfin
        st["fin_active"] = st["fin_active"] | ma
        if fullpath:
            st["fin_path"] = jnp.where(mfin[:, None], st["prow"][src],
                                       st["fin_path"])
        return st

    def flush_and_repack(st):
        """Flush ghosts + tombstones out of the pool, then gather-repack
        the surviving live lanes to the front — all selects and gathers."""
        lane = st["lane"]
        nonlive = (lane >= 0) & ~st["alive"]
        st = flush_into(st, nonlive, jnp.zeros((p,), bool))
        lane = jnp.where(nonlive, -1, lane)
        live = lane >= 0
        keys = ("lane", "cur", "prev", "info", "ring", "h", "prow")
        packed, pvalid = take_ranked(
            {kk: (lane if kk == "lane" else st[kk]) for kk in keys}, live, p)
        sel = lambda a, o: jnp.where(
            pvalid if a.ndim == 1 else pvalid[:, None], a, o)
        st["lane"] = jnp.where(pvalid, packed["lane"], -1)
        st["alive"] = pvalid
        st["term"] = jnp.zeros((p,), bool)
        st["cur"] = sel(packed["cur"], jnp.zeros_like(st["cur"]))
        st["prev"] = sel(packed["prev"], jnp.zeros_like(st["prev"]))
        st["info"] = jax.tree_util.tree_map(
            lambda a: jnp.where(pvalid, a, 0.0), packed["info"])
        st["ring"] = sel(packed["ring"], jnp.zeros_like(st["ring"]))
        st["h"] = sel(packed["h"], jnp.zeros_like(st["h"]))
        st["prow"] = jnp.where(pvalid[:, None], packed["prow"], -1)
        return st

    def superstep(st):
        """One flat BSP superstep — straight-line code, no inner control
        flow on the default transport. Globally-dead supersteps (the tail
        of an unrolled block) are value-level no-ops with ``t`` frozen."""
        lane = st["lane"]
        occ = (lane >= 0) & st["alive"]      # ghosts/tombstones don't walk
        ls = jnp.maximum(lane, 0)
        live_n = lax.psum(jnp.sum(occ, dtype=jnp.int32), AXIS)
        stepping = (live_n > 0) & (st["t"] < step_cap)
        u1f, u2f = ufn(root_key, st["t"])
        u1, u2 = u1f[ls], u2f[ls]

        # ---- phase A on the local slice ------------------------------------
        cur = st["cur"]
        cur_l = jnp.clip(local_of[cur], 0, max_nodes - 1)
        deg = (shard.indptr[cur_l + 1]
               - shard.indptr[cur_l]).astype(jnp.float32)
        deg = jnp.where(occ, deg, 0.0)                 # free slots are stale
        has_nbrs = deg > 0
        j = jnp.minimum((u1 * deg).astype(jnp.int32),
                        jnp.maximum(deg.astype(jnp.int32) - 1, 0))
        eidx = jnp.clip(shard.indptr[cur_l].astype(jnp.int32) + j,
                        0, max_edges - 1)
        cand = shard.indices[eidx]                     # global neighbor id
        cand_owner = shard.nbr_owner[eidx]             # halo remap: owner()
        p_acc = policy.accept_prob_local(shard, st["prev"], cur_l, cand, eidx)
        accept_raw = has_nbrs & (u2 < p_acc)
        accept = occ & accept_raw & stepping
        dead_end = occ & ~has_nbrs & stepping
        mig = accept & (cand_owner != sid)
        stay = accept & ~mig

        prow = st["prow"]
        if fullpath:
            # Pre-append the accepted node at the origin (the message
            # carries the walk INCLUDING it) — one-hot select, no scatter.
            idxL = jnp.clip(st["info"].L.astype(jnp.int32), 0,
                            spec.max_len - 1)
            lpos = jnp.arange(spec.max_len, dtype=jnp.int32)[None, :]
            prow = jnp.where(accept[:, None] & (lpos == idxL[:, None]),
                             cand[:, None], prow)
            ship_sz = jnp.sum((prow >= 0).astype(jnp.int32), axis=1)

        # ---- packed sparse exchange ----------------------------------------
        info = st["info"]
        pay = {"i": jnp.stack([lane, cur, cand], axis=1),
               "f": jnp.stack([info.H, info.L, info.EH, info.EL, info.EHL,
                               info.EH2, info.EL2], axis=1)}
        if spec.reg_window:
            pay["ring"] = st["ring"]
        if fullpath:
            pay["path"] = prow
            pay["h"] = st["h"]
        shipped_fields = pay["i"].shape[1] + pay["f"].shape[1] + (
            pay["ring"].shape[1] if spec.reg_window else 0)

        n_mig = jnp.sum(mig.astype(jnp.int32))
        if fullpath:
            add_an = jnp.sum(jnp.where(
                mig, incom.fullpath_msg_bytes(info.L + 1.0), 0.0))
        else:
            add_an = jnp.float32(incom.MSG_BYTES
                                 + 8 * (spec.reg_window or 0)) * n_mig

        sp0 = dict(
            pending=mig, lane=lane, alive=st["alive"], term=st["term"],
            cur=cur, prev=st["prev"],
            info=info, ring=st["ring"], h=st["h"], prow=prow,
            proc=stay, pcand=cand,
            overflow=jnp.zeros((), jnp.int32),
            msg_count=jnp.zeros((), jnp.int32),
            msg_bytes=jnp.zeros((), jnp.float32),
        )

        def sp_round(c):
            if transport == "a2a":
                # Destination-bucketed point-to-point swap (mesh path):
                # every received record is addressed to this shard.
                arr, arr_valid, sent = packed_all_to_all(
                    pay, cand_owner, c["pending"], k, r_cap, AXIS)
                mine = arr_valid.reshape(n_rec)
            elif transport == "gather":
                # Packed broadcast: receivers filter records by the
                # candidate's owner, recomputed from the record.
                arr, arr_valid, sent = packed_all_gather(
                    pay, c["pending"], r_cap, AXIS)
                cand_flat = arr["i"].reshape(n_rec, 3)[:, 2]
                mine = arr_valid.reshape(n_rec) & (
                    owner[jnp.maximum(cand_flat, 0)] == sid)
            else:
                # Flat pool transport (stacked default): the P-wide lane
                # payload travels masked — one round ALWAYS delivers every
                # migrant, so the superstep stays straight-line code.
                sent = c["pending"]
                arr = jax.tree_util.tree_map(
                    lambda x: lax.all_gather(x, AXIS), pay)
                a_lane = arr["i"].reshape(n_rec, 3)[:, 0]
                a_cand = arr["i"].reshape(n_rec, 3)[:, 2]
                pend_all = lax.all_gather(c["pending"], AXIS
                                          ).reshape(n_rec)
                mine = pend_all & (
                    owner[jnp.maximum(a_cand, 0)] == sid) & (a_lane >= 0)
            a_i = arr["i"].reshape(n_rec, 3)
            a_f = arr["f"].reshape(n_rec, 7)

            if fullpath:
                # The walk left with its walker; the sender slot frees.
                lane1 = jnp.where(sent, -1, c["lane"])
                revived = jnp.zeros((p,), bool)
                rrec = jnp.zeros((p,), jnp.int32)
                rec_unrevived = mine
            else:
                # The sender slot becomes a fragment GHOST: the walker's
                # owner-local path rows stay (they never travel) so a
                # returning walker can resume its n(v) history; the rows
                # retire to the store at the next flush. A RETURNING
                # walker REVIVES its own ghost slot in place — no free
                # slot needed, which is what keeps per-shard occupancy
                # bounded by one slot per lane (so pool == B never
                # overflows) and the fragment row simply stays put.
                lane1 = c["lane"]
                ghost = (lane1 >= 0) & ~c["alive"] & ~c["term"]
                rl = a_i[:, 0]
                rm = (lane1[:, None] == rl[None, :]) \
                    & mine[None, :] & ghost[:, None]     # (P, n_rec)
                revived = jnp.any(rm, axis=1)
                rrec = jnp.argmax(rm, axis=1).astype(jnp.int32)
                rec_unrevived = mine & ~jnp.any(rm, axis=0)
            alive1 = c["alive"] & ~sent
            free = lane1 < 0
            # Gather-based placement for first-visit arrivals: the r-th
            # free slot (ascending index) takes the r-th unrevived record
            # addressed to me (ascending (source shard, record position)
            # order) — scatter-free and deterministic, so walks never
            # depend on the transport.
            free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
            mcum = jnp.cumsum(rec_unrevived.astype(jnp.int32))
            n_mine = mcum[-1]
            takes = free & (free_rank < n_mine)
            rec_idx = jnp.clip(rank_search(mcum, free_rank + 1),
                               0, n_rec - 1)
            place = takes | revived
            rec_sel = jnp.where(revived, rrec, rec_idx)
            t_i = a_i[rec_sel]                          # (P, 3)
            t_f = a_f[rec_sel]                          # (P, 7)
            a_info = incom.InfoState(
                H=t_f[:, 0], L=t_f[:, 1], EH=t_f[:, 2], EL=t_f[:, 3],
                EHL=t_f[:, 4], EH2=t_f[:, 5], EL2=t_f[:, 6])

            if fullpath:
                prow1 = jnp.where(
                    takes[:, None],
                    arr["path"].reshape(n_rec, spec.max_len)[rec_sel],
                    c["prow"])
            else:
                # First-visit (or post-flush return) fragment rows come
                # from the lane-indexed store; a revived slot's row is
                # already in place. Resolved PER SLOT (P-sized — the
                # record axis is k·cap wide and row ops there blow up k^2
                # under the stacked emulation).
                t_lane = jnp.where(takes, t_i[:, 0], 0)
                prow1 = jnp.where(takes[:, None], st["frag"][t_lane],
                                  c["prow"])

            out = dict(
                pending=c["pending"] & ~sent,
                lane=jnp.where(takes, t_i[:, 0], lane1),
                alive=alive1 | place,
                term=c["term"] & ~place,
                cur=jnp.where(place, t_i[:, 1], c["cur"]),
                prev=jnp.where(place, t_i[:, 1], c["prev"]),
                info=_info_select(place, a_info, c["info"]),
                ring=(jnp.where(place[:, None],
                                arr["ring"].reshape(n_rec, k_ring)[rec_sel],
                                c["ring"])
                      if spec.reg_window else c["ring"]),
                h=(jnp.where(place[:, None],
                             arr["h"].reshape(n_rec, h_len)[rec_sel],
                             c["h"])
                   if fullpath else c["h"]),
                prow=prow1,
                proc=c["proc"] | place,
                pcand=jnp.where(place, t_i[:, 2], c["pcand"]),
                overflow=c["overflow"]
                + jnp.maximum(n_mine - jnp.sum(free, dtype=jnp.int32), 0),
            )
            n_sent = jnp.sum(sent, dtype=jnp.int32)
            if fullpath:
                shipped = jnp.sum(jnp.where(sent, ship_sz, 0))
                add_meas = (8.0 * pay["i"].shape[1]) * n_sent + 8.0 * shipped
            else:
                add_meas = jnp.float32(8.0 * shipped_fields) * n_sent
            out["msg_count"] = c["msg_count"] + n_sent
            out["msg_bytes"] = c["msg_bytes"] + add_meas
            return out

        if flat:
            sp = sp_round(sp0)     # one round always delivers everything
        else:
            def sp_cond(c):
                n = jnp.sum(c["pending"], dtype=jnp.int32)
                return lax.psum(n, AXIS) > 0

            # Spill rounds: self-skips when no shard has a migrant, loops
            # while more than ``cap`` migrants queue at one sender.
            sp = lax.while_loop(sp_cond, sp_round, sp0)

        # ---- phase B on the compacted pool ---------------------------------
        lane_x, proc, pcand = sp["lane"], sp["proc"], sp["pcand"]
        occ_now = jnp.sum((lane_x >= 0).astype(jnp.int32))
        info2, path2, h2, ring2, done_now = wk.absorb(
            spec, sp["info"], sp["prow"], sp["h"], sp["ring"], pcand, proc)
        cur2 = jnp.where(proc, pcand, sp["cur"])
        prev2 = jnp.where(proc, sp["cur"], sp["prev"])
        done = (proc & done_now) | dead_end

        nxt = dict(st)
        nxt.update(
            lane=lane_x,
            # Terminated walkers tombstone: state freezes in the pool and
            # retires to the fin buffers at the block flush.
            alive=sp["alive"] & (lane_x >= 0) & ~done,
            term=sp["term"] | done,
            cur=cur2, prev=prev2, info=info2, ring=ring2, h=h2, prow=path2,
            t=st["t"] + stepping.astype(jnp.int32),
            accepts=st["accepts"] + jnp.sum(accept, dtype=jnp.int32),
            rejects=st["rejects"]
            + jnp.sum(occ & has_nbrs & ~accept_raw & stepping,
                      dtype=jnp.int32),
            msg_count=st["msg_count"] + sp["msg_count"],
            msg_bytes=st["msg_bytes"] + sp["msg_bytes"],
            msg_bytes_analytic=st["msg_bytes_analytic"] + add_an,
            overflow=st["overflow"] + sp["overflow"],
            peak_occ=jnp.maximum(st["peak_occ"], occ_now),
        )
        return nxt

    def cond(st):
        live = jnp.sum((st["lane"] >= 0) & st["alive"], dtype=jnp.int32)
        return (lax.psum(live, AXIS) > 0) & (st["t"] < step_cap)

    def body(st):
        # ``unroll`` straight-line supersteps, then ONE unconditional
        # flush/repack: no lax.cond in the loop (its operand threading
        # copied every buffer every superstep), and the block tail runs as
        # cheap no-op supersteps when the walk ends mid-block.
        for _ in range(unroll):
            st = superstep(st)
        return flush_and_repack(st)

    out = lax.while_loop(cond, body, st0)

    # ---- final flush: ghosts, tombstones AND still-live lanes --------------
    filled = out["lane"] >= 0
    out = flush_into(out, filled, out["alive"])
    out["occ_final"] = jnp.sum(filled.astype(jnp.int32))
    out.pop("alive")
    out.pop("term")
    out.pop("prow")
    return out


# ---------------------------------------------------------------------------
# Drivers: stacked emulation (vmap) and SPMD (shard_map), both engines
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("policy", "spec", "num_shards"))
def _run_stacked(graph, owner, sources, root_key, policy, spec, num_shards):
    def per_shard(_marker):
        return _shard_program_replicated(graph, owner, sources, root_key,
                                         policy, spec)

    return jax.vmap(per_shard, axis_name=AXIS)(jnp.arange(num_shards))


@functools.partial(jax.jit,
                   static_argnames=("policy", "spec", "num_shards", "mesh"))
def _run_spmd(graph, owner, sources, root_key, policy, spec,
              num_shards: int, mesh: Mesh):
    def per_shard(graph_, owner_, sources_, key_, _marker):
        out = _shard_program_replicated(graph_, owner_, sources_, key_,
                                        policy, spec)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(AXIS)),
        out_specs=P(AXIS),
        check_vma=False,
    )
    return fn(graph, owner, sources, root_key, jnp.arange(num_shards))


@functools.partial(jax.jit,
                   static_argnames=("policy", "spec", "num_shards", "pool",
                                    "cap", "compact_every", "transport"))
def _run_stacked_local(slices, local_of, owner, sources, root_key,
                       policy, spec, num_shards, pool, cap, compact_every,
                       transport):
    def per_shard(shard):
        return _shard_program_local(shard, local_of, owner, sources, root_key,
                                    policy, spec, num_shards, pool, cap,
                                    compact_every, transport)

    return jax.vmap(per_shard, axis_name=AXIS)(slices)


@functools.partial(jax.jit,
                   static_argnames=("policy", "spec", "num_shards", "mesh",
                                    "pool", "cap", "compact_every",
                                    "transport"))
def _run_spmd_local(slices, local_of, owner, sources, root_key,
                    policy, spec, num_shards: int, mesh: Mesh,
                    pool: int, cap: int, compact_every: int, transport: str):
    def per_shard(slices_, local_of_, owner_, sources_, key_):
        out = _shard_program_local(
            slices_.take_shard(), local_of_, owner_, sources_, key_,
            policy, spec, num_shards, pool, cap, compact_every, transport)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    fn = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS), P(), P(), P(), P()),
        out_specs=P(AXIS),
        check_vma=False,
    )
    return fn(slices, local_of, owner, sources, root_key)


# ---------------------------------------------------------------------------
# Merges
# ---------------------------------------------------------------------------


def _merge(out, spec: wk.WalkSpec, root_key) -> wk.WalkerBatchState:
    """Combine the (k, ...) replicated-engine outputs into one state."""
    res = out["resident"]                                    # (k, B)
    pick = lambda x: jnp.sum(jnp.where(res, x, 0), axis=0)   # 1 resident/lane
    pickf = lambda x: jnp.sum(
        jnp.where(res[..., None], x, 0), axis=0)
    if spec.info_mode == "fullpath":
        # The walk travels whole; only the final resident copy is current.
        path = jnp.max(jnp.where(res[..., None], out["path"], -1), axis=0)
    else:
        # Fragment union: each position was written by exactly one owner.
        path = jnp.max(out["path"], axis=0)
    info = incom.InfoState(
        H=pick(out["info"].H), L=pick(out["info"].L),
        EH=pick(out["info"].EH), EL=pick(out["info"].EL),
        EHL=pick(out["info"].EHL), EH2=pick(out["info"].EH2),
        EL2=pick(out["info"].EL2))
    return wk.WalkerBatchState(
        cur=pick(out["cur"].astype(jnp.int32)),
        prev=pick(out["prev"].astype(jnp.int32)),
        path=path,
        info=info,
        h_series=pickf(out["h"]),
        hring=pickf(out["ring"]),
        active=jnp.any(out["resident"] & out["active"], axis=0),
        key=root_key,
        supersteps=jnp.max(out["t"]),        # max, not [0]: shard skew safe
        accepts=jnp.sum(out["accepts"]),
        rejects=jnp.sum(out["rejects"]),
        msg_count=jnp.sum(out["msg_count"]),
        msg_bytes=jnp.sum(out["msg_bytes"]),
        msg_bytes_analytic=jnp.sum(out["msg_bytes_analytic"]),
    )


def _merge_local(out, spec: wk.WalkSpec, root_key) -> wk.WalkerBatchState:
    """Combine the (k, ...) compacted-engine outputs into one state.

    Each lane retired (or was flushed) at EXACTLY one shard — the one whose
    ``fin_valid`` row is set — so the scalar merge is the same
    one-resident-per-lane sum the replicated merge uses; the path is the
    fragment union (incom) or the retiring copy (fullpath)."""
    fv = out["fin_valid"]                                    # (k, B)
    pick = lambda x: jnp.sum(jnp.where(fv, x, 0), axis=0)
    pickf = lambda x: jnp.sum(jnp.where(fv[..., None], x, 0), axis=0)
    if spec.info_mode == "fullpath":
        path = jnp.max(jnp.where(fv[..., None], out["fin_path"], -1), axis=0)
    else:
        path = jnp.max(out["frag"], axis=0)
    fi = out["fin_info"]
    info = incom.InfoState(
        H=pick(fi.H), L=pick(fi.L), EH=pick(fi.EH), EL=pick(fi.EL),
        EHL=pick(fi.EHL), EH2=pick(fi.EH2), EL2=pick(fi.EL2))
    return wk.WalkerBatchState(
        cur=pick(out["fin_cur"]),
        prev=pick(out["fin_prev"]),
        path=path,
        info=info,
        h_series=pickf(out["fin_h"]),
        hring=pickf(out["fin_ring"]),
        active=jnp.any(fv & out["fin_active"], axis=0),
        key=root_key,
        supersteps=jnp.max(out["t"]),        # max, not [0]: shard skew safe
        accepts=jnp.sum(out["accepts"]),
        rejects=jnp.sum(out["rejects"]),
        msg_count=jnp.sum(out["msg_count"]),
        msg_bytes=jnp.sum(out["msg_bytes"]),
        msg_bytes_analytic=jnp.sum(out["msg_bytes_analytic"]),
    )


def _shard_stats(out, pcsr: Optional[PartitionedCSR], pool: Optional[int],
                 cap: Optional[int], retries: int) -> Dict:
    """Per-shard balance/occupancy/traffic stats (benchmark surface)."""
    stats: Dict = {
        "supersteps": np.asarray(out["t"]).astype(int).tolist(),
        "msg_count": np.asarray(out["msg_count"]).astype(int).tolist(),
    }
    if "peak_occ" in out:
        stats["peak_lane_occupancy"] = (
            np.asarray(out["peak_occ"]).astype(int).tolist())
        stats["final_lane_occupancy"] = (
            np.asarray(out["occ_final"]).astype(int).tolist())
        stats["pool_slots"] = pool
        stats["exchange_cap"] = cap
        stats["pool_retries"] = retries
    if pcsr is not None:
        stats["owned_nodes"] = pcsr.num_owned.astype(int).tolist()
        stats["csr_bytes_per_shard"] = pcsr.shard_csr_nbytes().astype(
            int).tolist()
    # Everything above was already pulled to host for the stats dict;
    # exporting it to the registry adds no device syncs.
    if obs.enabled():
        obs.inc("walk.supersteps", float(np.sum(stats["supersteps"])))
        obs.inc("walk.msg_count", float(np.sum(stats["msg_count"])))
        if "peak_lane_occupancy" in stats:
            obs.set_gauges("walk.peak_occ", stats["peak_lane_occupancy"])
            obs.set_gauge("walk.pool_slots", stats["pool_slots"])
            obs.inc("walk.pool_retries", stats["pool_retries"])
        if "csr_bytes_per_shard" in stats:
            obs.set_gauges("walk.csr_bytes", stats["csr_bytes_per_shard"])
    return stats


# ---------------------------------------------------------------------------
# Partition-local store cache + public driver
# ---------------------------------------------------------------------------


_PCSR_CACHE: Dict = {}
_POOL_CACHE: Dict = {}


def partitioned_csr_for(graph: CSRGraph, assignment: np.ndarray,
                        num_shards: int,
                        key_obj: object = None,
                        mesh: Optional[Mesh] = None) -> PartitionedCSR:
    """Memoized ``build_partitioned_csr`` — the slicing is host-side O(|E|)
    preprocessing and the engine is called once per walk batch per round.

    ``key_obj`` names the object whose identity keys the cache; pass the
    CALLER-HELD graph when ``graph`` is a derived copy (e.g. the result of
    ``with_edge_cm()``, which is a fresh object every call and would never
    hit). Entries hold the key object by WEAKREF so a dropped graph's
    device-resident slices free with it, and the key carries the slicing
    graph's edge_cm presence so a cm-less entry is never served to a
    policy that needs Cm. The key also carries the graph's MUTATION
    VERSION (``graph.delta.graph_version``): a graph mutated through the
    delta overlay bumps its version, so an in-place edit of a held object
    can never be served the pre-mutation slices (identity alone would
    silently alias them). With ``mesh`` the slices are placed once, one
    shard per device of the mesh, and cached placed."""
    import weakref
    from repro.graph.delta import graph_version
    key_obj = graph if key_obj is None else key_obj
    asn = np.asarray(assignment)
    key = (id(key_obj), graph_version(key_obj), num_shards,
           graph.edge_cm is not None, hash(asn.tobytes()), mesh)
    hit = _PCSR_CACHE.get(key)
    if hit is not None and hit[0]() is key_obj:
        return hit[1]
    pcsr = build_partitioned_csr(graph, asn, num_shards)
    if mesh is not None:
        pcsr = dataclasses.replace(pcsr, slices=jax.device_put(
            pcsr.slices, NamedSharding(mesh, P(AXIS))))
    if len(_PCSR_CACHE) >= 8:
        _PCSR_CACHE.clear()
    _PCSR_CACHE[key] = (weakref.ref(key_obj), pcsr)
    return pcsr


def run_walk_sharded(
    graph: CSRGraph,
    sources: jax.Array,
    key: jax.Array,
    policy: Policy,
    spec: wk.WalkSpec,
    assignment: jax.Array,
    num_shards: int,
    mesh: Optional[Mesh] = None,
    *,
    engine: str = "auto",
    pool_factor: float = 2.0,
    exchange_cap: Optional[int] = None,
    compact_every: int = 8,
    transport: Optional[str] = None,
    with_stats: bool = False,
):
    """Run one walk per source on ``num_shards`` partition shards.

    ``assignment`` maps node -> owning shard (MPGP output). With ``mesh``
    (k devices) the program runs SPMD under shard_map; otherwise the k
    shards run as a stacked vmap axis on the local device. Results are
    bit-identical across both executions and across shard counts.

    ``engine`` picks the realization: ``"local"`` (partition-local CSR
    slices + compacted lane pool + packed sparse exchange), ``"replicated"``
    (full-width reference), or ``"auto"`` — local whenever the policy can
    evaluate its transition from one shard's slice
    (``policy.supports_partition_local``). ``pool_factor`` is the gamma of
    the MPGP balance bound sizing the per-shard slot pool
    (pool = gamma·B/k, doubled and re-run on the rare occupancy overflow);
    ``exchange_cap`` bounds records per source per spill round (per
    (source, destination) bucket under the all_to_all transport).
    ``transport`` forces the exchange realization — ``"gather"``
    (all_gather broadcast, the stacked default) or ``"a2a"``
    (destination-bucketed ``lax.all_to_all``, the mesh default); walks are
    bit-identical under either. ``with_stats=True`` additionally returns
    the per-shard balance/occupancy/traffic dict.
    """
    sources = jnp.asarray(sources, jnp.int32)
    owner = jnp.asarray(assignment, jnp.int32)
    graph_key = graph          # caches key on the CALLER's (stable) object
    if getattr(policy, "needs_edge_cm", False) and graph.edge_cm is None:
        graph = graph.with_edge_cm()
    if mesh is not None and int(mesh.shape[AXIS]) != num_shards:
        raise ValueError(
            f"mesh has {int(mesh.shape[AXIS])} {AXIS!r} devices for "
            f"{num_shards} shards")
    use_mesh = mesh is not None
    if engine == "auto":
        # Partition-local is the memory-correct engine when shards map to
        # real devices (each holds only its |V|/k + |E|/k slice). Under the
        # single-device stacked emulation there is no memory to save and
        # the k per-shard programs serialize, so the replicated fast path
        # wins wall-clock; tests/benchmarks pass engine="local" explicitly.
        engine = ("local"
                  if use_mesh
                  and getattr(policy, "supports_partition_local", False)
                  else "replicated")

    if engine == "replicated":
        if use_mesh:
            out = _run_spmd(graph, owner, sources, key, policy, spec,
                            num_shards, mesh)
        else:
            out = _run_stacked(graph, owner, sources, key, policy, spec,
                               num_shards)
        state = _merge(out, spec, key)
        if with_stats:
            return state, _shard_stats(out, None, None, None, 0)
        return state
    if engine != "local":
        raise ValueError(f"unknown engine {engine!r}")
    if not getattr(policy, "supports_partition_local", False):
        raise ValueError(
            f"{type(policy).__name__} cannot run partition-local (it reads "
            "non-local CSR rows); use engine='replicated'")

    asn_np = np.asarray(assignment)
    pcsr = partitioned_csr_for(graph, asn_np, num_shards, key_obj=graph_key,
                               mesh=mesh)
    b = int(sources.shape[0])
    init_occ = np.bincount(asn_np[np.asarray(sources)],
                           minlength=num_shards) if b else np.zeros(1)
    pool = min(b, max(int(np.ceil(pool_factor * b / max(num_shards, 1))),
                      int(init_occ.max()), 1))
    # Occupancy (live + ghosts + tombstones between flushes) is workload-
    # dependent; the overflow retry discovers the working pool size and
    # this cache remembers it, so steady-state callers (benchmark reps,
    # streaming rounds) run the engine exactly once per batch. Entries
    # weakly hold the keying graph so a recycled id() can never alias and
    # dead graphs don't pin memory.
    import weakref
    from repro.graph.delta import graph_version
    pool_key = (id(graph_key), graph_version(graph_key), num_shards, b,
                spec, float(pool_factor), hash(asn_np.tobytes()))
    hit = _POOL_CACHE.get(pool_key)
    if hit is not None and hit[0]() is graph_key:
        pool = max(pool, hit[1])
    cap = int(exchange_cap) if exchange_cap else max(8, pool // 8)
    if transport is None:
        # a2a = point-to-point buckets on a real mesh; the packed broadcast
        # is the stacked default — its spill loop constant-folds away when
        # a shard count makes migration impossible and self-skips on
        # migrant-free supersteps, unlike the flat "pool" transport which
        # pays its all_gather every superstep.
        transport = "a2a" if use_mesh else "gather"
    if transport not in ("pool", "gather", "a2a"):
        raise ValueError(f"unknown transport {transport!r}")

    retries = 0
    t0 = time.perf_counter() if obs.enabled() else 0.0
    while True:
        if use_mesh:
            out = _run_spmd_local(
                pcsr.slices, pcsr.local_of, owner, sources, key, policy,
                spec, num_shards, mesh, pool, cap, compact_every, transport)
        else:
            out = _run_stacked_local(
                pcsr.slices, pcsr.local_of, owner, sources, key, policy,
                spec, num_shards, pool, cap, compact_every, transport)
        if int(jnp.sum(out["overflow"])) == 0:
            break
        # MPGP balance bound violated at this pool size: walkers piled onto
        # one shard beyond gamma·B/k. Double the pool and re-run — at
        # pool == B overflow is impossible (arrivals + residents <= B).
        assert pool < b, "slot pool of size B cannot overflow"
        pool = min(b, pool * 2)
        retries += 1
    if retries:
        if len(_POOL_CACHE) >= 64:
            _POOL_CACHE.clear()
        _POOL_CACHE[pool_key] = (weakref.ref(graph_key), pool)
    if obs.enabled():
        # The overflow check above already synced the dispatch; the wall
        # measured here is real device time, not just enqueue latency.
        obs.observe("walk.batch_dispatch.s", time.perf_counter() - t0)
        obs.inc("walk.engine_batches")
        obs.inc("walk.spill_retries", retries)
        obs.set_gauge("walk.pool_slots", pool)
    state = _merge_local(out, spec, key)
    if with_stats:
        return state, _shard_stats(out, pcsr, pool, cap, retries)
    return state


def reconfigure_partitions(
    graph: CSRGraph,
    old_assignment: np.ndarray,
    new_assignment: np.ndarray,
    num_shards_new: int,
    *,
    old_of_new: np.ndarray,
    num_shards_old: Optional[int] = None,
    key_obj: object = None,
) -> Dict:
    """Swap the cached partition-local store to a new shard layout after
    an elastic reconfiguration (DESIGN.md §12) — a k → k-1 shard death
    (the default: ``num_shards_old`` falls back to ``num_shards_new + 1``)
    or a k → k+1 re-JOIN (pass ``num_shards_old`` explicitly, with a
    ``-1`` entry in ``old_of_new`` for the returned shard).

    Looks up the old ``PartitionedCSR`` in the cache; when found (the
    steady-state case — the walk engine built it on the previous round),
    the new store is assembled by ``reassign_partitioned_csr`` with the
    untouched shards' edge slices copied instead of re-scattered.
    Otherwise it falls back to a fresh ``build_partitioned_csr``. The new
    store is PRIMED into the cache under the new assignment's key so the
    next walk round hits, and every cache entry keyed on the replaced
    assignment — partition slices and learned slot-pool sizes — is
    evicted (the pool sizing of a k-way layout says nothing about k±1).

    Returns ``{"reused_shards", "rebuilt_shards", "wall_s"}``.
    """
    import time
    import weakref

    from repro.graph.csr import reassign_partitioned_csr
    from repro.graph.delta import graph_version

    t0 = time.perf_counter()
    key_obj = graph if key_obj is None else key_obj
    old_asn = np.asarray(old_assignment)
    new_asn = np.asarray(new_assignment)
    gv = graph_version(key_obj)
    k_old = (num_shards_new + 1 if num_shards_old is None
             else int(num_shards_old))
    h_old = hash(old_asn.tobytes())

    # Find a live old entry whose feature set (weights/cm presence) matches
    # the graph we are slicing — reuse needs like-for-like rows. The cm flag
    # in the key tracks the SLICING graph, which run_walk_sharded may have
    # cm-augmented, so match on the store itself rather than the flag.
    old_pcsr = None
    for key, (ref, pcsr) in list(_PCSR_CACHE.items()):
        if (key[0] == id(key_obj) and key[1] == gv and key[2] == k_old
                and key[4] == h_old and ref() is key_obj
                and (pcsr.slices.edge_cm is not None)
                == (graph.edge_cm is not None)
                and (pcsr.slices.weights is not None)
                == (graph.weights is not None)):
            old_pcsr = pcsr
            break

    if old_pcsr is not None:
        new_pcsr, reused = reassign_partitioned_csr(
            graph, new_asn, num_shards_new, old=old_pcsr,
            old_assignment=old_asn, old_of_new=np.asarray(old_of_new))
    else:
        new_pcsr, reused = build_partitioned_csr(
            graph, new_asn, num_shards_new), 0

    # Evict everything keyed on the dead layout, then prime the new one.
    for key in [k for k in _PCSR_CACHE
                if k[0] == id(key_obj) and k[4] == h_old]:
        del _PCSR_CACHE[key]
    for key in [k for k in _POOL_CACHE
                if k[0] == id(key_obj) and k[-1] == h_old]:
        del _POOL_CACHE[key]
    new_key = (id(key_obj), gv, num_shards_new, graph.edge_cm is not None,
               hash(new_asn.tobytes()), None)
    if len(_PCSR_CACHE) >= 8:
        _PCSR_CACHE.clear()
    _PCSR_CACHE[new_key] = (weakref.ref(key_obj), new_pcsr)

    return {
        "reused_shards": int(reused),
        "rebuilt_shards": int(num_shards_new - reused),
        "wall_s": float(time.perf_counter() - t0),
    }
