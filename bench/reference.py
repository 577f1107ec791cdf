"""Plain references for the cells' checks, written from the algorithms'
descriptions and importing nothing of the program.

- DSGL: DistGER's skip-gram with negative sampling over multi-window
  lifetimes (Section 4): per lifetime, gather the rows of its W walks and
  of K negatives per position, run the position-by-position SGNS updates on
  those local copies, and write the deltas back with the duplicates of one
  row averaged. Negatives are drawn as the configuration states: unigram
  counts to the power 0.75, through a Vose alias table built here from a
  recount of the corpus, with the chunk's key split once per lifetime.
- Initial tables: word2vec's convention, phi_in uniform in (-0.5/d, 0.5/d)
  and phi_out zero, from the key that the seed gives.
- Scores: the left-to-right float32 sum over d of the elementwise products,
  which the serving path promises bit for bit.

Each takes a ``dtype`` so that the same code, run one precision below the
configuration's, is the control that a check has to fail.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

MAX_LOGIT = 6.0       # word2vec's MAX_EXP clip


def jax_seed(seed: int) -> int:
    """A 32-bit key seed from any whole number, as the harness draws it."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


# ---------------------------------------------------------------------------
# Negative sampling
# ---------------------------------------------------------------------------

def alias_table(counts: np.ndarray, power: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Vose's alias method over counts**power: (accept probability, alias),
    the stack order fixed so that every table made from the same counts draws
    the same samples from the same key."""
    w = np.asarray(counts, np.float64) ** power
    if w.sum() == 0:
        w = np.ones_like(w)
    n = len(w)
    scaled = w / w.sum() * n
    prob = np.ones(n, np.float64)
    alias = np.arange(n, dtype=np.int64)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = l
        scaled[l] = (scaled[l] + scaled[s]) - 1.0
        (small if scaled[l] < 1.0 else large).append(l)
    for i in small + large:
        prob[i] = 1.0
    return prob.astype(np.float32), alias.astype(np.int32)


def draw_negatives(prob, alias, key, shape):
    """Alias draws: one uniform slot, one uniform accept test."""
    import jax
    import jax.numpy as jnp

    k_slot, k_acc = jax.random.split(key)
    slot = jax.random.randint(k_slot, shape, 0, prob.shape[0], dtype=jnp.int32)
    u = jax.random.uniform(k_acc, shape, jnp.float32)
    return jnp.where(u < prob[slot], slot, alias[slot])


# ---------------------------------------------------------------------------
# DSGL
# ---------------------------------------------------------------------------

def init_tables(key, num_nodes: int, dim: int, dtype=np.float32):
    import jax
    import jax.numpy as jnp

    phi_in = (jax.random.uniform(key, (num_nodes, dim), jnp.float32)
              - 0.5) / dim
    return phi_in.astype(dtype), jnp.zeros((num_nodes, dim), dtype)


def _position(carry, p, *, window: int, lr):
    """SGNS updates at position p of one lifetime, on its local rows."""
    import jax
    import jax.numpy as jnp

    ctx, out, neg, valid, loss = carry
    w_cnt, t_len, dim = ctx.shape
    k = neg.shape[1]
    offs = jnp.array([o for o in range(-window, window + 1) if o != 0])
    pos = p + offs                                         # (2w,)
    inside = (pos >= 0) & (pos < t_len)
    pos_c = jnp.clip(pos, 0, t_len - 1)
    ctx_ok = inside[None, :] & valid[:, pos_c]             # (W, 2w)
    tgt_ok = valid[:, p]                                   # (W,)
    rows = ctx[:, pos_c, :].reshape(w_cnt * 2 * window, dim)
    cols = jnp.concatenate([out[:, p, :], neg[p]], axis=0)  # (W+K, d)
    owner = jnp.repeat(jnp.arange(w_cnt), 2 * window)
    row_w = (ctx_ok.reshape(-1) & tgt_ok[owner]).astype(ctx.dtype)
    col_w = jnp.concatenate([tgt_ok.astype(ctx.dtype),
                             jnp.ones((k,), ctx.dtype)])
    mask = row_w[:, None] * col_w[None, :]
    label = (jnp.arange(w_cnt + k)[None, :] == owner[:, None]).astype(
        ctx.dtype)
    score = jax.nn.sigmoid(jnp.clip(rows @ cols.T, -MAX_LOGIT, MAX_LOGIT))
    eps = 1e-7
    bce = -(label * jnp.log(score + eps)
            + (1 - label) * jnp.log(1 - score + eps))
    loss = loss + jnp.sum(bce * mask).astype(jnp.float32)
    grad = (label - score) * mask
    d_rows = (grad @ cols) * lr
    d_cols = (grad.T @ rows) * lr
    ctx = ctx.at[:, pos_c, :].add(
        d_rows.reshape(w_cnt, 2 * window, dim).astype(ctx.dtype))
    out = out.at[:, p, :].add(d_cols[:w_cnt].astype(out.dtype))
    neg = neg.at[p].add(d_cols[w_cnt:].astype(neg.dtype))
    return (ctx, out, neg, valid, loss), None


def _lifetime(ctx, out, neg, valid, lr, window: int):
    import jax
    import jax.numpy as jnp

    step = functools.partial(_position, window=window, lr=lr)
    (ctx, out, neg, _, loss), _ = jax.lax.scan(
        step, (ctx, out, neg, valid, jnp.float32(0.0)),
        jnp.arange(ctx.shape[1]))
    return ctx, out, neg, loss


def _average_into(table, ids, deltas, keep):
    """table[ids] += deltas, each row's deltas averaged over its copies."""
    import jax.numpy as jnp

    keep = keep.astype(jnp.float32)
    copies = jnp.zeros(table.shape[0], jnp.float32).at[ids].add(keep)
    scale = keep / jnp.maximum(copies[ids], 1.0)
    return table.at[ids].add((deltas * scale[:, None]).astype(table.dtype))


def sgns_chunk(phi_in, phi_out, walks, prob, alias, key, lrs, *,
               window: int, negatives: int):
    """C lifetimes in order. ``walks`` is (C, G, W, T), -1 padded; returns
    the tables after the chunk and each lifetime's summed loss (C,)."""
    import jax
    import jax.numpy as jnp

    g_cnt, w_cnt, t_len = walks.shape[1:]

    def lifetime(carry, inp):
        pin, pout, k = carry
        wk, lr = inp
        k, sub = jax.random.split(k)
        negs = draw_negatives(prob, alias, sub,
                              (1, g_cnt, t_len, negatives))[0]
        valid = wk >= 0
        ids = jnp.maximum(wk, 0)
        ctx0, out0, neg0 = pin[ids], pout[ids], pout[negs]
        ctx, out, neg, loss = jax.vmap(
            lambda c, o, n, v: _lifetime(c, o, n, v, lr, window))(
                ctx0, out0, neg0, valid)
        flat, keep = ids.reshape(-1), valid.reshape(-1)
        pin = _average_into(pin, flat, (ctx - ctx0).reshape(flat.size, -1),
                            keep)
        nflat = negs.reshape(-1)
        pout = _average_into(
            pout, jnp.concatenate([flat, nflat]),
            jnp.concatenate([(out - out0).reshape(flat.size, -1),
                             (neg - neg0).reshape(nflat.size, -1)]),
            jnp.concatenate([keep, jnp.ones(nflat.size, bool)]))
        return (pin, pout, k), jnp.sum(loss)

    (phi_in, phi_out, _), losses = jax.lax.scan(
        lifetime, (phi_in, phi_out, key), (walks, lrs.astype(phi_in.dtype)))
    return phi_in, phi_out, losses


def sgns_chunk_jit():
    import jax
    return jax.jit(sgns_chunk, static_argnames=("window", "negatives"),
                   donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Scores
# ---------------------------------------------------------------------------

def chain_scores(query: np.ndarray, rows: np.ndarray, dtype=np.float32
                 ) -> np.ndarray:
    """Left-to-right sum over d of query * row, in ``dtype``."""
    prod = np.asarray(rows, dtype) * np.asarray(query, dtype)[None, :]
    acc = prod[:, 0].copy()
    for j in range(1, prod.shape[1]):
        acc = acc + prod[:, j]
    return acc


def topk(phi: np.ndarray, u: int, k: int, dtype=np.float32, block=16384
         ) -> Tuple[np.ndarray, np.ndarray]:
    """(scores, ids) of the k best vertices for u, u excluded, ties to the
    lower id."""
    q = phi[u]
    scores = np.concatenate([chain_scores(q, phi[i:i + block], dtype)
                             for i in range(0, len(phi), block)])
    scores = scores.astype(np.float32)
    scores[u] = -np.inf
    ids = np.argsort(-scores, kind="stable")[:k]
    return scores[ids], ids
