"""GPipe-style pipeline parallelism over one mesh axis.

Stage s of an S-stage pipeline lives on device s of the ``axis`` ring
(stage params sharded ``P(axis)`` on their leading dim). The input batch is
split into M microbatches; the classic (S + M - 1)-tick schedule keeps
every device busy once the pipeline fills, and a ``ppermute`` ring shifts
activations stage -> stage + 1 each tick. Forward matches the sequential
composition of the stages exactly, and reverse-mode differentiates through
the ppermute ring, so grads match the sequential program too (both are
asserted by tests/test_dist.py on 8 fake devices).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def microbatch(x: jax.Array, num_microbatches: int) -> jax.Array:
    """(M*mb, ...) -> (M, mb, ...) microbatch stream."""
    m = num_microbatches
    if x.shape[0] % m != 0:
        raise ValueError(
            f"batch {x.shape[0]} not divisible into {m} microbatches")
    return x.reshape(m, x.shape[0] // m, *x.shape[1:])


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,        # pytree, leaves (S, ...) — leading dim = stage
    xs: jax.Array,            # (M, mb, ...) microbatch stream
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run ``xs`` through S pipelined stages; returns (M, mb, ...) outputs."""
    num_stages = int(mesh.shape[axis])
    num_micro = int(xs.shape[0])
    ring = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def body(w_blk, stream):
        # w_blk leaves are (1, ...): this device's stage parameters.
        w = jax.tree_util.tree_map(lambda a: a[0], w_blk)
        stage_id = jax.lax.axis_index(axis)
        state = jnp.zeros_like(stream[0])
        outs = jnp.zeros_like(stream)
        for tick in range(num_stages + num_micro - 1):
            feed = stream[tick] if tick < num_micro else jnp.zeros_like(
                stream[0])
            inp = jnp.where(stage_id == 0, feed, state)
            out = stage_fn(w, inp)
            slot = tick - (num_stages - 1)
            if slot >= 0:
                done = jnp.where(stage_id == num_stages - 1, out,
                                 jnp.zeros_like(out))
                outs = outs.at[slot].add(done)
            state = jax.lax.ppermute(out, axis, ring)
        # Only the last stage wrote non-zeros; psum replicates its stream.
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    )(stage_params, xs)
