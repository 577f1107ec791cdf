"""shard_map/psum forms of the paper's cross-replica exchanges.

``hotness_sync_spmd`` is the SPMD realization of §4.2-III: every device
holds its own replica of the frequency-ordered embedding matrices; one sync
period averages exactly the sampled hotness rows across the replica axis
(O(blocks · d · m) bytes, not O(|V| · d · m)). ``repro.core.sync`` holds
the logical replica-list form with identical semantics.

``compressed_allreduce`` is a top-|g| sparsified all-reduce with error
feedback (residual carried to the next step) — the gradient-volume analogue
of the hotness idea, available to the LM training configs.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def hotness_sync_spmd(
    phi_in: jax.Array,    # (N, d) f32 — this replica's matrix (replicated spec)
    phi_out: jax.Array,   # (N, d) f32
    rows: jax.Array,      # (R,) int32 sampled hotness rows
    mesh: Mesh,
    axis: str,
) -> Tuple[jax.Array, jax.Array, float]:
    """Average the sampled rows across the ``axis`` replicas and write them
    back into both matrices. Returns (phi_in', phi_out', bytes_moved)."""
    m = int(mesh.shape[axis])

    def body(pi, po, r):
        mean_in = jax.lax.pmean(pi[r], axis)
        mean_out = jax.lax.pmean(po[r], axis)
        return pi.at[r].set(mean_in), po.at[r].set(mean_out)

    pi2, po2 = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )(phi_in, phi_out, rows)
    dim = int(phi_in.shape[-1])
    nbytes = float(int(rows.shape[0]) * dim * 4 * m * 2)
    return pi2, po2, nbytes


def psum_union(tree, mask: jax.Array, axis: str):
    """Exactly-one-sender union exchange over a named axis.

    Every shard contributes its leaves masked by ``mask`` (lanes it is
    sending); the psum reconstructs each lane's payload EXACTLY — including
    negative sentinel values — because at most one shard sends any lane per
    round (all other contributions are literal zeros). This is the
    collective behind the walk engine's InCoM message hand-off
    (``repro.core.shard_engine``): one all-reduce moves the packed
    constant-size messages, and the byte volume measured from the masked
    rows is the paper's Example-1 traffic.

    Must be called inside shard_map / vmap with ``axis`` bound. ``mask`` is
    broadcast against each leaf's leading dimensions.
    """
    def one(x):
        m = mask
        while m.ndim < x.ndim:
            m = m[..., None]
        return jax.lax.psum(jnp.where(m, x, jnp.zeros((), x.dtype)), axis)

    return jax.tree_util.tree_map(one, tree)


def rank_search(csum: jax.Array, queries: jax.Array) -> jax.Array:
    """Unrolled vectorized lower-bound search: for each q in ``queries``
    the first index i with csum[i] >= q. Plain selects + gathers — no
    lax.scan/while (jnp.searchsorted's scan lowering inside a vmapped
    while-loop measured ~1 ms/call on CPU; this is ~10 fused vector ops).
    ``csum`` must be non-decreasing (a mask cumsum)."""
    n = csum.shape[0]
    lo = jnp.zeros(queries.shape, jnp.int32)
    hi = jnp.full(queries.shape, n, jnp.int32)
    for _ in range(max(n, 1).bit_length()):      # ceil(log2(n + 1)) halvings
        mid = (lo + hi) // 2
        go = csum[jnp.clip(mid, 0, n - 1)] < queries
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(go, hi, mid)
    return lo


def take_ranked(payload, mask: jax.Array, count: int):
    """Gather-compact the first ``count`` mask-set lanes, scatter-free.

    Slot j of the output holds the j-th mask-set lane (ascending lane
    order): one cumsum + one vectorized binary search + one gather per
    leaf — no scatter (XLA CPU scatters serialize; this path runs inside
    the walk superstep). Returns (packed leaves with leading dim
    ``count``, valid (count,) bool)."""
    csum = jnp.cumsum(mask.astype(jnp.int32))
    n = csum[-1] if mask.shape[0] else jnp.int32(0)
    j = jnp.arange(count, dtype=jnp.int32)
    src = jnp.clip(rank_search(csum, j + 1), 0, max(mask.shape[0] - 1, 0))
    valid = j < n
    packed = jax.tree_util.tree_map(lambda x: x[src], payload)
    return packed, valid


def packed_all_gather(
    payload,              # pytree of (P, ...) per-lane leaves
    pending: jax.Array,   # (P,) bool — lanes that still need to ship
    cap: int,             # max records per source shard per round
    axis: str,
):
    """Compacted sparse exchange, broadcast transport (stacked path).

    Each shard gather-compacts up to ``cap`` of its pending lanes into a
    (cap, ...) record buffer and one ``lax.all_gather`` publishes it:
    every shard receives (k, cap, ...) — k·cap·fields wire volume instead
    of the dense all-lane psum. Receivers filter records by destination
    themselves (the destination is derivable from the record, e.g.
    owner[cand]). Lanes beyond ``cap`` stay pending for the caller's next
    spill round.

    Returns ``(records, valid, sent)``: records leaves (k, cap, ...) with
    row s = shard s's packed batch, ``valid`` (k, cap) bool, ``sent`` the
    (P,) bool mask of lanes this shard shipped this round.
    """
    rank = jnp.cumsum(pending.astype(jnp.int32)) - 1
    sent = pending & (rank < cap)
    packed, valid = take_ranked(payload, pending, cap)
    records, arr_valid = jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, axis), (packed, valid))
    return records, arr_valid, sent


def packed_all_to_all(
    payload,              # pytree of (P, ...) per-lane leaves
    dest: jax.Array,      # (P,) int32 destination shard per lane
    pending: jax.Array,   # (P,) bool — lanes that still need to ship
    num_shards: int,
    cap: int,             # max records per (source, destination) pair
    axis: str,
):
    """Compacted sparse migrant exchange over a named axis.

    Each shard prefix-scans its ``pending`` lanes per destination, scatters
    the first ``cap`` of each bucket into a (k, cap, ...) send buffer, and
    one ``lax.all_to_all`` swaps the buckets — shard d receives row s =
    the records shard s addressed to d. Wire volume is O(k · cap · fields)
    per shard instead of the dense all-lane psum the walk engine used
    before; lanes beyond ``cap`` stay pending and ship on the caller's next
    spill round (``sent`` reports what left this round, so the caller's
    spill loop terminates: every non-empty bucket moves >= 1 record).

    Works identically under ``vmap`` (stacked emulation — all_to_all has a
    batching rule over named axes) and ``shard_map`` (real point-to-point
    collectives on a mesh).

    Returns ``(arrivals, arr_valid, sent)``: arrivals leaves are
    (k, cap, ...) with row s = records from shard s (zero-filled where
    invalid), ``arr_valid`` is the matching (k, cap) bool validity mask,
    ``sent`` the (P,) bool mask of lanes this shard shipped.
    """
    k = num_shards
    onehot = (dest[None, :] == jnp.arange(k, dtype=dest.dtype)[:, None]) \
        & pending[None, :]                                       # (k, P)
    rank = jnp.cumsum(onehot.astype(jnp.int32), axis=1) - 1      # (k, P)
    rank_of = jnp.sum(jnp.where(onehot, rank, 0), axis=0)        # (P,)
    sent = pending & (rank_of < cap)
    slot = jnp.where(sent, dest * cap + rank_of, k * cap)        # OOB = drop

    def pack(x):
        buf = jnp.zeros((k * cap,) + x.shape[1:], x.dtype)
        buf = buf.at[slot].set(x, mode="drop")
        return buf.reshape((k, cap) + x.shape[1:])

    packed = jax.tree_util.tree_map(pack, payload)
    valid = pack(sent)
    arrivals, arr_valid = jax.tree_util.tree_map(
        lambda b: jax.lax.all_to_all(b, axis, split_axis=0, concat_axis=0),
        (packed, valid))
    return arrivals, arr_valid, sent


def local_mesh(num_devices: int, axis: str) -> Mesh:
    """A 1-axis mesh over the first ``num_devices`` local devices. Raises
    when the host has fewer: a caller that asked for a mesh must never get
    the stacked one-device emulation in its place without knowing."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < num_devices:
        raise RuntimeError(
            f"a {num_devices}-device {axis!r} mesh needs {num_devices} "
            f"devices; this host has {len(devs)} "
            f"({devs[0].platform if devs else 'none'})")
    return Mesh(np.asarray(devs[:num_devices]), (axis,))


def compressed_allreduce(
    grad: jax.Array,      # per-shard gradient block
    error: jax.Array,     # per-shard error-feedback residual (same shape)
    ratio: float,         # fraction of entries to keep (0 < ratio <= 1)
    axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k sparsified all-reduce with error feedback.

    Must be called INSIDE shard_map: keeps the largest-|.| ``ratio`` fraction
    of (grad + error), pmeans only those entries across ``axis``, and returns
    the dense synced result plus the residual to carry forward. The sparse
    part + residual always equals grad + error exactly (no signal is lost,
    only delayed)."""
    acc = grad + error
    flat = acc.reshape(-1)
    k = max(int(ratio * flat.shape[0]), 1)
    topk = jax.lax.top_k(jnp.abs(flat), k)[0]
    thresh = topk[-1]
    mask = (jnp.abs(flat) >= thresh).astype(acc.dtype).reshape(acc.shape)
    sparse = acc * mask
    residual = acc - sparse
    synced = jax.lax.pmean(sparse, axis)
    return synced, residual
