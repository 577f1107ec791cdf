"""Pallas TPU kernel: fused SGNS lifetime update (paper §4.2-I/II on MXU).

One grid program processes one *lifetime* (a group of W = multi_windows
walks). The three local buffers — context rows (phi_in), target rows and the
negative-sample rows (phi_out) — are VMEM-resident for the whole lifetime:
loaded once, updated in-place across all T positions, stored once. This is
the TPU mapping of the paper's "local buffers reduce cache-line
ping-ponging": HBM traffic is one read + one write per row per lifetime
regardless of how many windows touch the row.

Per position and walk (the W loop is static and unrolled) the fused
pipeline runs on values in VMEM/VREGs:
    logits ((2w+1) x (W+K) MXU matmul) -> clamp(+-6) -> sigmoid ->
    gradient -> SGD update of both buffers.

Windows are read and written back through ref slices (``pl.ds``) on a
(T + 2w)-padded time axis — no value-level dynamic slices, gathers or
scatters, which Mosaic does not lower. The window's center row is masked
out instead of excluded, which is mathematically identical. The per-group
loss is a scalar in SMEM.

VMEM budget per program (W=2, T=100+2w, d=128, K=5, f32):
  ctx/out: 2*120*128*4 = 123 KiB each; neg: 100*5*128*4 = 256 KiB (sublane
  padding of K to 8 makes it 400 KiB); valid: 2*120*128*4 = 123 KiB
  lane-padded -> ~0.8 MiB per copy, double-buffered in and out.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_EXP = 6.0


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _sgns_kernel(
    lr_ref,     # (1, 1) f32, SMEM
    ctx_ref,    # (1, W, Tp, d)  phi_in rows, time-padded by w on both sides
    out_ref,    # (1, W, Tp, d)  phi_out rows (same padding)
    neg_ref,    # (1, T, K, d)
    valid_ref,  # (1, W, Tp, 1) f32 (0/1), same padding
    ctx_o_ref, out_o_ref, neg_o_ref,
    loss_ref,   # (G,) f32, SMEM, whole array; this program writes slot g
    t_rows_ref,  # scratch (W+K, d): this position's targets then negatives
    *, window: int, t_len: int,
):
    w = window
    _, w_cnt, _, dim = ctx_ref.shape
    k = neg_ref.shape[2]
    span = 2 * w + 1
    n_t = w_cnt + k
    f32 = jnp.float32
    lr = lr_ref[0, 0]

    ctx_o_ref[...] = ctx_ref[...]
    out_o_ref[...] = out_ref[...]
    neg_o_ref[...] = neg_ref[...]

    # Static bookkeeping: column j < W is walk j's target, the rest are the
    # shared negatives; row w of a window is its (masked-out) center.
    col = jax.lax.broadcasted_iota(jnp.int32, (span, n_t), 1)
    col_row = jax.lax.broadcasted_iota(jnp.int32, (1, n_t), 1)
    not_center = (
        jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0) != w).astype(f32)
    eps = 1e-7

    def body(p, loss):
        c_wins = [ctx_o_ref[0, i, pl.ds(p, span), :] for i in range(w_cnt)]
        v_wins = [valid_ref[0, i, pl.ds(p, span), :] for i in range(w_cnt)]
        # (1, 1) -> (1, W+K): Mosaic broadcasts lanes and sublanes apart.
        tgt_valid = [
            jnp.broadcast_to(valid_ref[0, i, pl.ds(p + w, 1), :], (1, n_t))
            for i in range(w_cnt)]
        for i in range(w_cnt):
            t_rows_ref[pl.ds(i, 1), :] = out_o_ref[0, i, pl.ds(p + w, 1), :]
        t_rows_ref[pl.ds(w_cnt, k), :] = neg_o_ref[0, p]
        t_rows = t_rows_ref[...]                                 # (W+K, d)

        col_mask = (col_row >= w_cnt).astype(f32)                # (1, W+K)
        for j in range(w_cnt):
            col_mask = col_mask + jnp.where(col_row == j, tgt_valid[j], 0.0)

        d_t = jnp.zeros((n_t, dim), f32)
        for i in range(w_cnt):
            c = c_wins[i]                                        # (2w+1, d)
            logits = jnp.clip(
                jax.lax.dot_general(
                    c, t_rows, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32),
                -MAX_EXP, MAX_EXP,
            )
            sig = jax.nn.sigmoid(logits)
            y = (col == i).astype(f32)
            mask = (v_wins[i] * not_center) * (tgt_valid[i] * col_mask)
            g = (y - sig) * mask
            pair_loss = -(y * jnp.log(sig + eps)
                          + (1 - y) * jnp.log(1 - sig + eps))
            loss = loss + jnp.sum(pair_loss * mask)
            d_c = jnp.dot(g, t_rows, preferred_element_type=f32) * lr
            d_t = d_t + jax.lax.dot_general(
                g, c, (((0,), (0,)), ((), ())), preferred_element_type=f32)
            ctx_o_ref[0, i, pl.ds(p, span), :] = c + d_c
        d_t = d_t * lr

        for i in range(w_cnt):
            out_o_ref[0, i, pl.ds(p + w, 1), :] = (
                t_rows[i:i + 1] + d_t[i:i + 1])
        neg_o_ref[0, p] = t_rows[w_cnt:] + d_t[w_cnt:]
        return loss

    loss_ref[pl.program_id(0)] = jax.lax.fori_loop(
        0, t_len, body, jnp.float32(0.0))


def sgns_lifetime_pallas(
    ctx_pad: jax.Array,   # (G, W, T+2w, d)
    out_pad: jax.Array,   # (G, W, T+2w, d)
    neg: jax.Array,       # (G, T, K, d)
    valid_pad: jax.Array, # (G, W, T+2w, 1) f32 (0/1)
    lr: jax.Array,        # (1, 1) f32
    *, window: int, t_len: int, interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    # Auto-detect like ops.py: compiled on TPU, interpreter elsewhere.
    # (A literal `interpret=True` default silently ran the interpreter on
    # TPU for direct callers.)
    if interpret is None:
        interpret = not on_tpu()
    g_cnt, w_cnt, t_pad, dim = ctx_pad.shape
    k = neg.shape[2]
    kernel = functools.partial(_sgns_kernel, window=window, t_len=t_len)
    rows = pl.BlockSpec((1, w_cnt, t_pad, dim), lambda g: (g, 0, 0, 0))
    negs = pl.BlockSpec((1, t_len, k, dim), lambda g: (g, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(g_cnt,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            rows, rows, negs,
            pl.BlockSpec((1, w_cnt, t_pad, 1), lambda g: (g, 0, 0, 0)),
        ],
        out_specs=[
            rows, rows, negs,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g_cnt, w_cnt, t_pad, dim), jnp.float32),
            jax.ShapeDtypeStruct((g_cnt, w_cnt, t_pad, dim), jnp.float32),
            jax.ShapeDtypeStruct((g_cnt, t_len, k, dim), jnp.float32),
            jax.ShapeDtypeStruct((g_cnt,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((w_cnt + k, dim), jnp.float32)],
        interpret=interpret,
    )(lr, ctx_pad, out_pad, neg, valid_pad)
