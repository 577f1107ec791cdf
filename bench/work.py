"""Operations and bytes that each kernel's algorithm needs, from its shapes.

These count the work the algorithm requires, not the work a given
implementation does, so a roofline share reads the same whichever code path
implements the layer, and padding or a materialised temporary shows as a
lower share.

DSGL (skip-gram with negative sampling over multi-window lifetimes, DistGER
Section 4). A lifetime trains W walks together; a walk of length L holds
valid positions 0..L-1. At position p, walk w takes part as a target when
p < L_w. Its context rows are the valid positions within ``window`` of p,
p itself excluded. The columns are the valid targets of all W walks (each
walk's target is a negative for the others) plus K shared negatives. Every
(context row, column) pair costs one dot product forward and two products
back, 3 x 2 x d operations. Each valid token reads and writes one row of
phi_in and one of phi_out; each position with a valid target reads and
writes its K negative rows of phi_out. Rows are float32.

Top-K scoring reads phi once and writes one score per (query, vertex), and
needs 2 x B x |V| x d operations.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

F32 = 4


def walk_lengths(walks: np.ndarray) -> np.ndarray:
    """Valid length of each walk of a (..., T) -1-padded array."""
    return np.sum(np.asarray(walks) >= 0, axis=-1)


def sgns_work(lengths: np.ndarray, window: int, negatives: int, dim: int
              ) -> Tuple[float, float]:
    """(operations, bytes) of the lifetimes whose walk lengths are
    ``lengths``, shape (lifetimes, W)."""
    lengths = np.asarray(lengths, np.int64).reshape(-1, np.shape(lengths)[-1])
    t_max = int(lengths.max(initial=0))
    flops = 0.0
    tokens = float(lengths.sum())
    live_positions = 0.0
    for p in range(t_max):
        target = p < lengths                                 # (N, W)
        hi = np.minimum(p + window, lengths - 1)
        lo = max(p - window, 0)
        contexts = np.where(target, hi - lo, 0)              # p excluded
        rows = contexts.sum(axis=1)
        cols = target.sum(axis=1) + negatives
        any_target = target.any(axis=1)
        flops += float(np.sum(np.where(any_target, rows * cols, 0)))
        live_positions += float(any_target.sum())
    flops *= 3 * 2 * dim
    bytes_ = (tokens * 2 * 2 + live_positions * negatives * 2) * dim * F32
    return flops, bytes_


def topk_work(batch: int, num_nodes: int, dim: int) -> Tuple[float, float]:
    """(operations, bytes) of scoring ``batch`` queries against every
    vertex: one read of phi, one score written per (query, vertex)."""
    flops = 2.0 * batch * num_nodes * dim
    bytes_ = float(num_nodes * dim * F32 + batch * num_nodes * F32)
    return flops, bytes_


def roofline_share(flops: float, bytes_: float, seconds: float, peaks: dict
                   ) -> Tuple[float, str]:
    """(percent of the roofline, the bound that binds): the least time the
    chip could take, over the time taken."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
