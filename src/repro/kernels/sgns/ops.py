"""Jit'd wrapper around the fused SGNS Pallas kernel.

Handles the time-axis padding the kernel wants (T -> T + 2w so windows are
plain ref slices; validity as a (T + 2w, 1) column so a window's mask is a
sublane slice too) and exposes the same call signature as the pure-jnp
reference (``ref.sgns_lifetime_batch_ref``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.sgns.kernel import on_tpu, sgns_lifetime_pallas


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def sgns_lifetime_batch(
    ctx: jax.Array,    # (G, W, T, d) f32
    out: jax.Array,    # (G, W, T, d) f32
    neg: jax.Array,    # (G, T, K, d) f32
    valid: jax.Array,  # (G, W, T) bool
    lr: jax.Array,     # () f32
    window: int,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused lifetime update for G groups. Returns (ctx, out, neg, loss(G,))."""
    if interpret is None:
        interpret = not on_tpu()
    g_cnt, w_cnt, t_len, dim = ctx.shape
    w = window
    pad = ((0, 0), (0, 0), (w, w), (0, 0))
    ctx_p = jnp.pad(ctx, pad)
    out_p = jnp.pad(out, pad)
    valid_p = jnp.pad(valid.astype(jnp.float32),
                      ((0, 0), (0, 0), (w, w)))[..., None]
    lr_arr = jnp.full((1, 1), lr, jnp.float32)
    ctx_p, out_p, neg_o, loss = sgns_lifetime_pallas(
        ctx_p, out_p, neg, valid_p, lr_arr,
        window=w, t_len=t_len, interpret=interpret,
    )
    return (
        ctx_p[:, :, w : w + t_len, :],
        out_p[:, :, w : w + t_len, :],
        neg_o,
        loss,
    )
