"""What the read drivers share: the served table, the server, the users.

The table is made on the device from ``--seed`` in one jitted call, saved in
the pipeline's snapshot format and offered to ``EmbedServer`` through
``offer_snapshot``, the path a serving process takes. The host keeps its
own copy for the reference.

Public names of the program used here: ``save_checkpoint``,
``EmbedServer`` (``offer_snapshot``, ``submit``, ``tick``), ``ServeConfig``.
"""

from __future__ import annotations

import functools
import tempfile
from typing import Dict, List, Tuple

import numpy as np

import common
import reference


def make_table(seed: int, num_nodes: int, dim: int) -> np.ndarray:
    """(|V|, d) float32 rows, normal with scale 1/sqrt(d), made on the
    device; returns the host copy."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def table(key, n, d):
        return jax.random.normal(key, (n, d), jnp.float32) / np.sqrt(d)

    phi = table(jax.random.PRNGKey(reference.jax_seed(seed)), num_nodes, dim)
    return np.asarray(phi)


def start_server(phi: np.ndarray, batch_slots: int):
    """An EmbedServer serving ``phi`` as snapshot 0."""
    from repro.ckpt.checkpoint import save_checkpoint
    from repro.runtime.serve import EmbedServer, ServeConfig

    server = EmbedServer(ServeConfig(batch_slots=batch_slots))
    with tempfile.TemporaryDirectory(prefix="bench_snapshot_") as root:
        save_checkpoint(root, 0, {"phi_in": phi[None]},
                        meta={"kind": "streaming_pipeline",
                              "graph_version": 0, "global_step": 0})
        if not server.offer_snapshot(root):
            raise RuntimeError("the server refused the snapshot")
    return server


class Users:
    """Zipf(s) user draws over node ids: rank r has weight 1/r**s, and the
    ranks are laid over the ids by a permutation from the seed."""

    def __init__(self, rng: np.random.Generator, num_nodes: int, s: float):
        w = 1.0 / np.arange(1, num_nodes + 1, dtype=np.float64) ** s
        self.cdf = np.cumsum(w / w.sum())
        self.ids = rng.permutation(num_nodes)
        self.rng = rng

    def draw(self, count: int) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, self.rng.random(count))
        return self.ids[np.minimum(ranks, len(self.ids) - 1)]


def check_topk(phi: np.ndarray, answers: List[Tuple[int, np.ndarray,
                                                     np.ndarray]],
               k: int, dtype=np.float32) -> Dict[str, int]:
    """Top-K answers (u, ids, scores) against the reference: each score
    must equal the reference's k best bit for bit, and each returned id's
    reference score must equal the score returned with it."""
    score_off = id_off = 0
    for u, ids, scores in answers:
        want, _ = reference.topk(phi, int(u), k, dtype)
        score_off += int(np.sum(np.asarray(scores, np.float32) != want))
        got = reference.chain_scores(phi[int(u)], phi[np.asarray(ids)], dtype)
        id_off += int(np.sum(got.astype(np.float32) != scores)
                      + np.sum(np.asarray(ids) == int(u)))
    return {"topk_score_mismatch": score_off, "topk_id_mismatch": id_off}


def check_pairs(phi: np.ndarray, answers, dtype=np.float32) -> Dict[str, int]:
    """Pair answers (u, candidates, ids, scores) against the reference."""
    off = 0
    for u, cand, ids, scores in answers:
        want = reference.chain_scores(phi[int(u)], phi[cand], dtype)
        off += int(np.sum(want.astype(np.float32) != scores)
                   + np.sum(np.asarray(ids) != cand))
    return {"pair_score_mismatch": off}


def check_list(found: Dict[str, int], sampled: int) -> List[common.Check]:
    checks = [common.Check(name, v, 0) for name, v in found.items()]
    return checks + [common.Check("answers_unchecked", 0 if sampled else 1,
                                  0)]
