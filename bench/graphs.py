"""The deployments' graphs: R-MAT with no isolated vertex, cached per checkout.

A configuration's ``graph`` block fixes the graph completely: vertex count,
R-MAT edge draws, initiator and the dataset seed. The graph is the
deployment's dataset, so it never depends on a run's ``--seed``.

R-MAT leaves many vertices without an edge (about half at YouTube scale),
and the real graphs it stands in for have none. So every vertex that R-MAT
leaves isolated gets one edge, to a vertex drawn in proportion to its
degree. The edge count drawn from R-MAT is chosen in the configuration so
that 2|E|/|V| after that step lands within 10% of the source's degree.

Building means R-MAT, the program's CSR construction and its HuGE
common-neighbour counts, all on the host: minutes at LiveJournal scale. The
result is kept under ``bench/.data/<config>-<key>/``, where the key hashes
the graph block and ``FORMAT``; a directory of the same configuration under
another key is stale and is removed when the graph is rebuilt.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
from typing import Dict, Optional

import numpy as np

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".data")
FORMAT = 1          # bump when what is cached, or how, changes
ARRAYS = ("indptr", "indices", "edge_cm", "arc_keys")


def rmat_edges(num_nodes: int, num_edges: int, initiator, seed: int
               ) -> np.ndarray:
    """(num_edges, 2) int64 R-MAT draws over ceil(log2 n) levels, ids taken
    mod ``num_nodes``. ``initiator`` is (a, b, c, d), the quadrant
    probabilities; Graph500's is (0.57, 0.19, 0.19, 0.05)."""
    a, b, c, d = (float(x) for x in initiator)
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"initiator {initiator} does not sum to 1")
    rng = np.random.default_rng(seed)
    scale = max(1, int(np.ceil(np.log2(max(num_nodes, 2)))))
    src = np.zeros(num_edges, np.int64)
    dst = np.zeros(num_edges, np.int64)
    for _ in range(scale):
        src_bit = rng.random(num_edges) < c + d
        p_dst = np.where(src_bit, d / (c + d), b / (a + b))
        dst_bit = rng.random(num_edges) < p_dst
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    return np.stack([src % num_nodes, dst % num_nodes], axis=1)


def undirected_arc_keys(edges: np.ndarray, num_nodes: int) -> np.ndarray:
    """Sorted unique ``u * n + v`` over both directions, self-loops dropped:
    the arc set that walks are checked against."""
    e = edges[edges[:, 0] != edges[:, 1]]
    keys = np.concatenate([e[:, 0] * num_nodes + e[:, 1],
                           e[:, 1] * num_nodes + e[:, 0]])
    return np.unique(keys)


def connect_isolated(edges: np.ndarray, num_nodes: int, seed: int
                     ) -> np.ndarray:
    """``edges`` plus one edge from each isolated vertex to a vertex drawn in
    proportion to its degree (so never to another isolated one)."""
    keys = undirected_arc_keys(edges, num_nodes)
    deg = np.bincount(keys // num_nodes, minlength=num_nodes)
    iso = np.flatnonzero(deg == 0)
    if len(iso) == 0:
        return edges
    rng = np.random.default_rng(seed)
    to = rng.choice(num_nodes, size=len(iso), p=deg / deg.sum())
    return np.concatenate([edges, np.stack([iso, to], axis=1)])


def edges_for(graph_cfg: Dict) -> np.ndarray:
    if graph_cfg["generator"] != "rmat":
        raise ValueError(f"unknown graph generator {graph_cfg['generator']!r}")
    n = int(graph_cfg["num_nodes"])
    seed = int(graph_cfg["dataset_seed"])
    edges = rmat_edges(n, int(graph_cfg["rmat_edges"]),
                       graph_cfg["initiator"], seed)
    return connect_isolated(edges, n, seed + 1)


def build(graph_cfg: Dict) -> Dict[str, np.ndarray]:
    """Every cached array of the graph: CSR and HuGE counts through the
    program's own code, and this module's independent arc keys."""
    from repro.graph.csr import build_csr

    n = int(graph_cfg["num_nodes"])
    edges = edges_for(graph_cfg)
    keys = undirected_arc_keys(edges, n)
    deg = np.bincount(keys // n, minlength=n)
    if np.any(deg == 0):
        raise ValueError("an isolated vertex is left after connect_isolated")
    avg = len(keys) / n
    want = float(graph_cfg["avg_degree"])
    if abs(avg - want) > 0.1 * want:
        raise ValueError(f"2|E|/|V| = {avg:.3f}, not within 10% of {want}; "
                         "change rmat_edges in the configuration")
    graph = build_csr(edges, n).with_edge_cm()
    return {"indptr": np.asarray(graph.indptr),
            "indices": np.asarray(graph.indices),
            "edge_cm": np.asarray(graph.edge_cm),
            "arc_keys": keys}


def cache_key(graph_cfg: Dict) -> str:
    blob = json.dumps({"format": FORMAT, "graph": graph_cfg}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def load(name: str, graph_cfg: Dict, data_dir: Optional[str] = None
         ) -> Dict[str, np.ndarray]:
    """The graph's arrays from the data cache, built on a miss. Returns the
    arrays and sets ``"cached"`` to whether they came from the cache."""
    data_dir = data_dir or DATA_DIR
    key = cache_key(graph_cfg)
    path = os.path.join(data_dir, f"{name}-{key}")
    if all(os.path.exists(os.path.join(path, f"{a}.npy")) for a in ARRAYS):
        out = {a: np.load(os.path.join(path, f"{a}.npy")) for a in ARRAYS}
        out["cached"] = True
        return out
    arrays = build(graph_cfg)
    os.makedirs(data_dir, exist_ok=True)
    stale = re.compile(re.escape(name) + r"-[0-9a-f]{16}(\.partial)?")
    for entry in os.listdir(data_dir):
        if stale.fullmatch(entry) and entry != f"{name}-{key}":
            shutil.rmtree(os.path.join(data_dir, entry), ignore_errors=True)
    tmp = f"{path}.partial"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for a in ARRAYS:
        np.save(os.path.join(tmp, f"{a}.npy"), arrays[a])
    shutil.rmtree(path, ignore_errors=True)
    os.rename(tmp, path)
    arrays["cached"] = False
    return arrays
