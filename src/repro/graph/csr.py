"""CSR graph container (paper §2: "DistGER uses the CSR format").

Undirected edges are stored twice (both directions), directed once, exactly
as the paper describes. Neighbor lists are kept **sorted** so that set
intersections (common-neighbor counts, MPGP proximity scores) can use
galloping/binary search.

The container is a pytree of device arrays so it can be donated to jitted
walk kernels, sharded, or kept on host as numpy transparently.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Compressed-sparse-row adjacency.

    indptr:   (|V|+1,) int32  — row offsets
    indices:  (|E|,)   int32  — sorted neighbor ids per row
    weights:  (|E|,)   float32 or None — edge weights (None = unweighted)
    edge_cm:  (|E|,)   int32 or None — per-edge common-neighbor counts
                                       (precomputed; see DESIGN.md §2)
    """

    indptr: jax.Array
    indices: jax.Array
    weights: Optional[jax.Array] = None
    edge_cm: Optional[jax.Array] = None

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.indptr, self.indices, self.weights, self.edge_cm), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- basic properties --------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def num_edges(self) -> int:
        """Number of stored directed arcs (2x undirected edge count)."""
        return int(self.indices.shape[0])

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    @property
    def max_degree(self) -> int:
        return int(np.max(np.asarray(self.degrees())))

    def neighbors(self, u: int) -> np.ndarray:
        lo, hi = int(self.indptr[u]), int(self.indptr[u + 1])
        return np.asarray(self.indices[lo:hi])

    def to_numpy(self) -> "CSRGraph":
        return CSRGraph(
            indptr=np.asarray(self.indptr),
            indices=np.asarray(self.indices),
            weights=None if self.weights is None else np.asarray(self.weights),
            edge_cm=None if self.edge_cm is None else np.asarray(self.edge_cm),
        )

    def to_device(self) -> "CSRGraph":
        return CSRGraph(
            indptr=jnp.asarray(self.indptr, jnp.int32),
            indices=jnp.asarray(self.indices, jnp.int32),
            weights=None if self.weights is None else jnp.asarray(self.weights, jnp.float32),
            edge_cm=None if self.edge_cm is None else jnp.asarray(self.edge_cm, jnp.int32),
        )

    def with_edge_cm(self) -> "CSRGraph":
        if self.edge_cm is not None:
            return self
        cm = edge_common_neighbors(self)
        return dataclasses.replace(self, edge_cm=jnp.asarray(cm, jnp.int32))


def build_csr(
    edges: np.ndarray,
    num_nodes: Optional[int] = None,
    *,
    undirected: bool = True,
    weights: Optional[np.ndarray] = None,
    dedup: bool = True,
) -> CSRGraph:
    """Build a CSR graph from an (m, 2) int edge array.

    Self-loops are dropped. With ``undirected=True`` each edge is stored in
    both directions (paper §2). Neighbor lists come out sorted.
    """
    edges = np.asarray(edges, dtype=np.int64)
    if edges.ndim != 2 or edges.shape[1] != 2:
        raise ValueError(f"edges must be (m, 2), got {edges.shape}")
    mask = edges[:, 0] != edges[:, 1]
    edges = edges[mask]
    w = None
    if weights is not None:
        w = np.asarray(weights, dtype=np.float32)[mask]

    if undirected:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
        if w is not None:
            w = np.concatenate([w, w], axis=0)

    if num_nodes is None:
        num_nodes = int(edges.max()) + 1 if edges.size else 0

    # Sort by (src, dst) so rows are contiguous and neighbor lists sorted.
    order = np.lexsort((edges[:, 1], edges[:, 0]))
    edges = edges[order]
    if w is not None:
        w = w[order]

    if dedup and edges.size:
        keep = np.ones(len(edges), dtype=bool)
        keep[1:] = np.any(edges[1:] != edges[:-1], axis=1)
        edges = edges[keep]
        if w is not None:
            w = w[keep]

    counts = np.bincount(edges[:, 0], minlength=num_nodes)
    indptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])

    return CSRGraph(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(edges[:, 1], jnp.int32),
        weights=None if w is None else jnp.asarray(w, jnp.float32),
        edge_cm=None,
    )


def edge_common_neighbors(graph: CSRGraph) -> np.ndarray:
    """Per-edge common-neighbor counts Cm(u, v), CSR-aligned.

    This is the cached form of the HuGE transition numerator (Eq. 3). On a
    symmetric CSR (every undirected graph ``build_csr`` makes) Cm(u, v) is
    the number of triangles through edge {u, v}, counted vectorized over
    degree-ordered wedges (``_edge_triangles``); any other CSR takes the
    per-arc intersection of ``edge_common_neighbors_ref``. Both give the
    same integers.
    """
    g = graph.to_numpy()
    indptr = np.asarray(g.indptr, np.int64)
    indices = np.asarray(g.indices, np.int64)
    n = len(indptr) - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    keys = src * n + indices                    # ascending: CSR row order
    rev = np.searchsorted(keys, indices * n + src)
    if len(keys) and np.array_equal(
            keys[np.minimum(rev, len(keys) - 1)], indices * n + src):
        return _edge_triangles(indptr, src, indices, keys, rev)
    return edge_common_neighbors_ref(graph)


def _edge_triangles(indptr, src, dst, keys, rev,
                    chunk: int = 1 << 23) -> np.ndarray:
    """Triangles per arc of a symmetric CSR. Each triangle is found once,
    from its lowest-ranked vertex under the (degree, id) order: for every
    pair of that vertex's higher-ranked neighbors, a binary search over the
    sorted arc keys tests whether the pair is an edge. The wedge count this
    enumerates is far below the sum over arcs of min(deg(u), deg(v)) that
    per-arc intersection costs on skewed graphs. Each found triangle adds 1
    to its six arcs (``rev`` maps an arc to its reverse)."""
    n = len(indptr) - 1
    m = len(dst)
    deg = np.diff(indptr)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    fwd = np.flatnonzero(rank[src] < rank[dst])     # arcs to higher rank
    f_src, f_dst = src[fwd], dst[fwd]
    d_plus = np.bincount(f_src, minlength=n)
    start = np.repeat(np.cumsum(d_plus) - d_plus, d_plus)
    later = (start + d_plus[f_src]) - np.arange(len(fwd)) - 1
    bounds = np.searchsorted(np.cumsum(later), np.arange(
        0, int(later.sum()) + chunk, chunk), side="right")
    hits = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi <= lo:
            continue
        cnt = later[lo:hi]
        first = np.repeat(np.arange(lo, hi), cnt)
        offs = np.arange(len(first)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        second = first + 1 + offs
        pair = f_dst[first] * n + f_dst[second]
        pos = np.minimum(np.searchsorted(keys, pair), m - 1)
        tri = keys[pos] == pair
        arcs = (fwd[first[tri]], fwd[second[tri]], pos[tri])
        hits.extend(arcs)
        hits.extend(rev[a] for a in arcs)
    if not hits:
        return np.zeros(m, np.int32)
    return np.bincount(np.concatenate(hits), minlength=m).astype(np.int32)


def edge_common_neighbors_ref(graph: CSRGraph) -> np.ndarray:
    """Per-arc Cm(u, v) by one sorted intersection per arc — the reference
    ``edge_common_neighbors`` is tested against, and its path for CSRs that
    are not symmetric."""
    g = graph.to_numpy()
    indptr, indices = g.indptr.astype(np.int64), g.indices.astype(np.int64)
    n = len(indptr) - 1
    cm = np.zeros(len(indices), dtype=np.int32)
    for u in range(n):
        lo, hi = indptr[u], indptr[u + 1]
        nu = indices[lo:hi]
        if nu.size == 0:
            continue
        for k in range(lo, hi):
            v = indices[k]
            nv = indices[indptr[v]:indptr[v + 1]]
            # galloping-style: binary-search the smaller set into the larger
            if nu.size <= nv.size:
                small, large = nu, nv
            else:
                small, large = nv, nu
            pos = np.searchsorted(large, small)
            pos = np.minimum(pos, large.size - 1)
            cm[k] = int(np.sum(large[pos] == small))
    return cm


def subgraph_partition_pad(
    graph: CSRGraph, assignment: np.ndarray, num_parts: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Split a CSR graph into per-partition padded CSR slices.

    Returns (indptr_p, indices_p, owned_nodes_p, max_nodes) where arrays are
    stacked per partition and padded so every partition has identical shapes
    (required for shard_map). Node ids stay GLOBAL; each partition stores the
    adjacency of the nodes it owns.
    """
    parts = _partition_slices(graph, assignment, num_parts)
    return (parts["indptr"], parts["indices"], parts["owned"],
            parts["max_nodes"])


def _partition_slices(
    graph: CSRGraph, assignment: np.ndarray, num_parts: int
) -> dict:
    """Vectorized per-partition CSR slicing (host numpy, O(|V| + |E|)).

    Within a partition, rows are ordered by ascending GLOBAL node id and
    each row keeps its sorted neighbor list, so the slice row for node v is
    bit-for-bit the global CSR row for v.
    """
    g = graph.to_numpy()
    indptr = np.asarray(g.indptr, np.int64)
    indices = np.asarray(g.indices, np.int64)
    n = len(indptr) - 1
    asn = np.asarray(assignment, np.int64)
    deg = indptr[1:] - indptr[:-1]

    counts = np.bincount(asn, minlength=num_parts)
    max_nodes = max(int(counts.max()), 1) if n else 1
    node_starts = np.zeros(num_parts + 1, np.int64)
    np.cumsum(counts, out=node_starts[1:])
    order = np.argsort(asn, kind="stable")       # ascending ids within part
    local_of = np.empty(max(n, 1), np.int64)
    local_of[order] = np.arange(n) - np.repeat(node_starts[:-1], counts)
    owned = np.full((num_parts, max_nodes), -1, np.int64)
    if n:
        owned[asn, local_of[:n]] = np.arange(n)

    deg_p = np.zeros((num_parts, max_nodes), np.int64)
    if n:
        deg_p[asn, local_of[:n]] = deg
    indptr_p = np.zeros((num_parts, max_nodes + 1), np.int64)
    np.cumsum(deg_p, axis=1, out=indptr_p[:, 1:])

    # Arcs grouped by partition; the original arc order is src-major with
    # ascending src, so a stable sort by partition keeps each partition's
    # arcs in ascending-local-row order — exactly the indptr_p layout.
    src = np.repeat(np.arange(n), deg)
    arc_order = np.argsort(asn[src], kind="stable") if len(src) else src
    e_counts = np.bincount(asn[src], minlength=num_parts).astype(np.int64)
    max_edges = max(int(e_counts.max()), 1) if len(src) else 1
    e_starts = np.zeros(num_parts + 1, np.int64)
    np.cumsum(e_counts, out=e_starts[1:])
    indices_p = np.full((num_parts, max_edges), -1, np.int64)
    arc_p = asn[src][arc_order]
    arc_pos = np.arange(len(src)) - np.repeat(e_starts[:-1], e_counts)
    dst = indices[arc_order]
    if len(src):
        indices_p[arc_p, arc_pos] = dst

    def edge_aligned(values, fill, dtype):
        out = np.full((num_parts, max_edges), fill, dtype)
        if len(src):
            out[arc_p, arc_pos] = values
        return out

    return {
        "indptr": indptr_p, "indices": indices_p, "owned": owned,
        "max_nodes": max_nodes, "local_of": local_of[:n].astype(np.int64),
        "num_owned": counts.astype(np.int64), "deg": deg,
        "arc_dst": dst, "edge_aligned": edge_aligned,
        "arc_order": arc_order,
    }


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardCSR:
    """Per-shard padded CSR slice in LOCAL row ids + edge-aligned halo
    metadata (DESIGN.md §9). Stacked form has a leading (k,) axis; inside a
    ``vmap``/``shard_map`` program the leading axis is mapped away and the
    same class holds one shard's slice.

    indptr:    (k, max_nodes+1) int32 — local row offsets
    indices:   (k, max_edges)   int32 — GLOBAL neighbor ids (-1 pad)
    nbr_owner: (k, max_edges)   int32 — owning shard of each neighbor (the
                                        halo remap: owner[] lookups for
                                        candidates never touch a global map)
    nbr_deg:   (k, max_edges)   int32 — degree of each neighbor (HuGE Eq. 3)
    weights:   (k, max_edges)   f32 or None — edge weights, slice-aligned
    edge_cm:   (k, max_edges)   int32 or None — Cm(u,v), slice-aligned
    """

    indptr: jax.Array
    indices: jax.Array
    nbr_owner: jax.Array
    nbr_deg: jax.Array
    weights: Optional[jax.Array] = None
    edge_cm: Optional[jax.Array] = None

    def tree_flatten(self):
        return (self.indptr, self.indices, self.nbr_owner, self.nbr_deg,
                self.weights, self.edge_cm), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def take_shard(self) -> "ShardCSR":
        """Drop the leading length-1 axis a shard_map block carries."""
        return jax.tree_util.tree_map(lambda x: x[0], self)


@dataclasses.dataclass(frozen=True)
class PartitionedCSR:
    """Host-level partition-local graph store: stacked ``ShardCSR`` slices
    plus the replicated O(|V|) node metadata the walk engine needs.

    ``slices`` scale as O(|V|/k + |E|/k) per shard — the memory the paper's
    per-partition cost model (Eq. 14–15) budgets per machine; ``local_of``
    (global node -> local row at its owner) is O(|V|) node metadata,
    replicated like the MPGP ``assignment`` itself.
    """

    slices: ShardCSR              # stacked (k, ...) device arrays
    local_of: jax.Array           # (|V|,) int32, replicated
    owned: np.ndarray             # (k, max_nodes) int64, host
    num_owned: np.ndarray         # (k,) int64, host
    num_parts: int

    def shard_csr_nbytes(self) -> np.ndarray:
        """Per-shard bytes of the CSR slice proper (indptr + indices +
        optional weights/cm) — the quantity BENCH_walk reports against the
        |V|/k + |E|/k model."""
        per = (self.slices.indptr.shape[-1] * 4
               + self.slices.indices.shape[-1] * 4)
        if self.slices.weights is not None:
            per += self.slices.weights.shape[-1] * 4
        if self.slices.edge_cm is not None:
            per += self.slices.edge_cm.shape[-1] * 4
        return np.full(self.num_parts, per, np.int64)


def build_partitioned_csr(
    graph: CSRGraph, assignment: np.ndarray, num_parts: int
) -> PartitionedCSR:
    """Build the partition-local store the sharded walk engine runs on.

    Each shard's slice holds the adjacency of the nodes it owns in local
    row ids, with neighbor ids kept global (they name the message
    destination and the path entry) and the per-edge halo metadata —
    neighbor owner and neighbor degree — precomputed so phase A never
    indexes a global O(|E|) array.
    """
    parts = _partition_slices(graph, assignment, num_parts)
    g = graph.to_numpy()
    asn = np.asarray(assignment, np.int64)
    deg = parts["deg"]
    dst = parts["arc_dst"]
    edge_aligned = parts["edge_aligned"]

    nbr_owner = edge_aligned(asn[dst] if len(dst) else dst, -1, np.int64)
    nbr_deg = edge_aligned(deg[dst] if len(dst) else dst, 0, np.int64)
    weights_p = None
    if g.weights is not None:
        w = np.asarray(g.weights, np.float32)[parts["arc_order"]]
        weights_p = edge_aligned(w, 0.0, np.float32)
    edge_cm_p = None
    if g.edge_cm is not None:
        cm = np.asarray(g.edge_cm, np.int64)[parts["arc_order"]]
        edge_cm_p = edge_aligned(cm, 0, np.int64)

    slices = ShardCSR(
        indptr=jnp.asarray(parts["indptr"], jnp.int32),
        indices=jnp.asarray(parts["indices"], jnp.int32),
        nbr_owner=jnp.asarray(nbr_owner, jnp.int32),
        nbr_deg=jnp.asarray(nbr_deg, jnp.int32),
        weights=None if weights_p is None else jnp.asarray(weights_p),
        edge_cm=None if edge_cm_p is None else jnp.asarray(edge_cm_p,
                                                           jnp.int32),
    )
    return PartitionedCSR(
        slices=slices,
        local_of=jnp.asarray(parts["local_of"], jnp.int32),
        owned=parts["owned"],
        num_owned=parts["num_owned"],
        num_parts=num_parts,
    )


def _fit_row(row: np.ndarray, width: int, fill) -> np.ndarray:
    """Pad (with ``fill``) or truncate a 1-D slice row to ``width``. Rebuilt
    partitions change the padded slice dims; survivor rows only ever gain or
    lose PADDING (their real entries always fit), so fit is lossless."""
    if row.shape[0] >= width:
        return row[:width]
    out = np.full(width, fill, dtype=row.dtype)
    out[:row.shape[0]] = row
    return out


def reassign_partitioned_csr(
    graph: CSRGraph,
    new_assignment: np.ndarray,
    num_parts: int,
    *,
    old: PartitionedCSR,
    old_assignment: np.ndarray,
    old_of_new: np.ndarray,
) -> Tuple[PartitionedCSR, int]:
    """Partial rebuild of a ``PartitionedCSR`` after elastic shard
    reconfiguration (DESIGN.md §12).

    Direction-agnostic: ``new_assignment`` is either the COMPACTED
    k-1-way assignment of a shard death (``mpgp.reassign_dead_shard`` +
    ``compact_assignment``) or the k+1-way assignment of a re-JOIN/split
    (``mpgp.rejoin_shard``). ``old`` is the store being replaced and
    ``old_of_new[s]`` maps new shard s back to its original shard id,
    with ``-1`` marking a brand-new shard (re-join). Shards whose node
    set is untouched — neither gained nodes nor (in the split direction)
    donated any — keep their O(|E|/k) slice rows (indices, nbr_deg,
    weights, edge_cm) copied from the old device slices (refit to the
    new padded dims) instead of re-scattered; only changed shards'
    rows rebuild, with the arc scatter masked to their arcs.
    ``nbr_owner`` is recomputed for EVERY shard (any edge into a moved
    node changes owner) straight from the slice's global neighbor ids.
    Node-level layout (owned/local_of/indptr) is O(|V|) vectorized and
    recomputed outright.

    Returns ``(store, reused)`` where ``reused`` counts survivor shards
    whose edge rows were copied, and the store is bit-identical to
    ``build_partitioned_csr(graph, new_assignment, num_parts)``.
    """
    g = graph.to_numpy()
    indptr = np.asarray(g.indptr, np.int64)
    indices = np.asarray(g.indices, np.int64)
    n = len(indptr) - 1
    asn = np.asarray(new_assignment, np.int64)
    old_asn = np.asarray(old_assignment, np.int64)
    old_of_new = np.asarray(old_of_new, np.int64)
    deg = indptr[1:] - indptr[:-1]

    # -- node-level layout (cheap, recomputed) ------------------------------
    counts = np.bincount(asn, minlength=num_parts)
    max_nodes = max(int(counts.max()), 1) if n else 1
    node_starts = np.zeros(num_parts + 1, np.int64)
    np.cumsum(counts, out=node_starts[1:])
    order = np.argsort(asn, kind="stable")
    local_of = np.empty(max(n, 1), np.int64)
    local_of[order] = np.arange(n) - np.repeat(node_starts[:-1], counts)
    owned = np.full((num_parts, max_nodes), -1, np.int64)
    if n:
        owned[asn, local_of[:n]] = np.arange(n)
    deg_p = np.zeros((num_parts, max_nodes), np.int64)
    if n:
        deg_p[asn, local_of[:n]] = deg
    indptr_p = np.zeros((num_parts, max_nodes + 1), np.int64)
    np.cumsum(deg_p, axis=1, out=indptr_p[:, 1:])

    e_counts = np.zeros(num_parts, np.int64)
    np.add.at(e_counts, asn, deg)
    num_edges = int(indptr[-1])
    max_edges = max(int(e_counts.max()), 1) if num_edges else 1

    # -- changed-shard detection (direction-agnostic) -----------------------
    # A node "moved" iff its old shard is not the old counterpart of its
    # new shard (a brand-new shard's -1 counterpart never matches, so all
    # its nodes are moved). A shard rebuilds iff it gained moved nodes
    # (the shard-death direction: orphans stream into survivors) OR, as a
    # surviving shard, lost some (the re-join/split direction: donors
    # stream out). Both reduce to the same two scatters.
    changed = np.zeros(num_parts, dtype=bool)
    if old_of_new.size:
        changed[old_of_new < 0] = True
    if n:
        moved = old_of_new[asn] != old_asn
        if moved.any():
            changed[np.unique(asn[moved])] = True            # gainers
            size = 1 + int(max(old_asn.max(),
                               old_of_new.max() if old_of_new.size else -1))
            new_of_old = np.full(size, -1, np.int64)
            keep = old_of_new >= 0
            new_of_old[old_of_new[keep]] = np.flatnonzero(keep)
            donors = new_of_old[old_asn[moved]]
            donors = donors[donors >= 0]                     # dead → gone
            if donors.size:
                changed[np.unique(donors)] = True            # losers

    has_w = old.slices.weights is not None
    has_cm = old.slices.edge_cm is not None
    indices_p = np.full((num_parts, max_edges), -1, np.int64)
    nbr_deg = np.zeros((num_parts, max_edges), np.int64)
    weights_p = np.zeros((num_parts, max_edges), np.float32) if has_w else None
    edge_cm_p = np.zeros((num_parts, max_edges), np.int64) if has_cm else None

    # -- survivors: copy edge rows from the old device slices ---------------
    reused = 0
    old_indices = np.asarray(old.slices.indices, np.int64)
    old_nbr_deg = np.asarray(old.slices.nbr_deg, np.int64)
    old_w = np.asarray(old.slices.weights, np.float32) if has_w else None
    old_cm = np.asarray(old.slices.edge_cm, np.int64) if has_cm else None
    for s in range(num_parts):
        if changed[s]:
            continue
        o = int(old_of_new[s])
        indices_p[s] = _fit_row(old_indices[o], max_edges, -1)
        nbr_deg[s] = _fit_row(old_nbr_deg[o], max_edges, 0)
        if has_w:
            weights_p[s] = _fit_row(old_w[o], max_edges, 0.0)
        if has_cm:
            edge_cm_p[s] = _fit_row(old_cm[o], max_edges, 0)
        reused += 1

    # -- gainers: masked arc scatter (O(|E_changed|)) -----------------------
    if n and changed.any():
        src = np.repeat(np.arange(n), deg)
        asn_src = asn[src]
        sel = np.flatnonzero(changed[asn_src])
        # Stable sort by shard keeps the ascending-src arc order within each
        # shard — the indptr_p row layout (see _partition_slices).
        sub = sel[np.argsort(asn_src[sel], kind="stable")]
        sub_p = asn_src[sub]
        sub_counts = np.bincount(sub_p, minlength=num_parts)
        sub_starts = np.zeros(num_parts + 1, np.int64)
        np.cumsum(sub_counts, out=sub_starts[1:])
        sub_pos = np.arange(len(sub)) - np.repeat(sub_starts[:-1], sub_counts)
        dst = indices[sub]
        indices_p[sub_p, sub_pos] = dst
        nbr_deg[sub_p, sub_pos] = deg[dst]
        if has_w:
            weights_p[sub_p, sub_pos] = np.asarray(g.weights,
                                                   np.float32)[sub]
        if has_cm:
            edge_cm_p[sub_p, sub_pos] = np.asarray(g.edge_cm, np.int64)[sub]

    # -- nbr_owner: global remap, recomputed for all shards -----------------
    valid = indices_p >= 0
    nbr_owner = np.where(valid, asn[np.where(valid, indices_p, 0)], -1)

    slices = ShardCSR(
        indptr=jnp.asarray(indptr_p, jnp.int32),
        indices=jnp.asarray(indices_p, jnp.int32),
        nbr_owner=jnp.asarray(nbr_owner, jnp.int32),
        nbr_deg=jnp.asarray(nbr_deg, jnp.int32),
        weights=None if weights_p is None else jnp.asarray(weights_p),
        edge_cm=None if edge_cm_p is None else jnp.asarray(edge_cm_p,
                                                           jnp.int32),
    )
    store = PartitionedCSR(
        slices=slices,
        local_of=jnp.asarray(local_of[:n], jnp.int32),
        owned=owned,
        num_owned=counts.astype(np.int64),
        num_parts=num_parts,
    )
    return store, reused
