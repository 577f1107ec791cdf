"""Distribution layer: spec resolution + multi-device (8 fake CPU devices,
subprocess) shard_map collectives, pipeline parallelism, sharded train step."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    BATCH_AXES, mesh_axis_size, resolve_spec, resolve_specs,
)
from repro.launch.mesh import make_host_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(body: str, n_devices: int = 8) -> str:
    """Run a snippet under --xla_force_host_platform_device_count."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# spec resolution (single device)
# ---------------------------------------------------------------------------

def test_resolve_spec_drops_missing_axes():
    mesh = make_host_mesh(1, 1)   # has data+model but sizes 1
    s = resolve_spec(P(("pod", "data"), "model"), mesh, (4, 4))
    assert s == P("data", "model")


def test_resolve_spec_drops_nondivisible():
    mesh = make_host_mesh(1, 1)
    # trivially divisible with size-1 axes
    assert resolve_spec(P("data"), mesh, (3,)) == P("data")


def test_resolve_specs_tree():
    mesh = make_host_mesh(1, 1)
    tree = {"a": P("pod", "model"), "b": {"c": P(("pod", "data"))}}
    out = resolve_specs(tree, mesh)
    assert out["a"] == P(None, "model")
    assert out["b"]["c"] == P("data")


def test_mesh_axis_size():
    mesh = make_host_mesh(1, 1)
    assert mesh_axis_size(mesh, None) == 1
    assert mesh_axis_size(mesh, "data") == 1
    assert mesh_axis_size(mesh, ("data", "model")) == 1


# ---------------------------------------------------------------------------
# multi-device subprocess tests
# ---------------------------------------------------------------------------

def test_hotness_sync_spmd_8dev():
    out = _run_subprocess("""
        from repro.dist.collectives import hotness_sync_spmd
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        n, d = 32, 4
        pi = jnp.arange(n * d, dtype=jnp.float32).reshape(n, d)
        po = -pi
        rows = jnp.array([0, 5, 31], jnp.int32)
        pi2, po2, nbytes = hotness_sync_spmd(pi, po, rows, mesh, "data")
        # replicated input -> pmean is identity
        assert np.allclose(np.asarray(pi2), np.asarray(pi)), "pi changed"
        print("OK", nbytes)
    """)
    assert "OK" in out


def test_pipeline_apply_matches_sequential_8dev():
    out = _run_subprocess("""
        from repro.dist.pipeline import microbatch, pipeline_apply
        S, M, mb, dim = 8, 4, 2, 16
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("pipe",))
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, dim, dim)) * (dim ** -0.5)
        x = jax.random.normal(jax.random.fold_in(key, 1), (M * mb, dim))

        def stage(p, h):
            return jnp.tanh(h @ p)

        # sequential reference
        ref = x
        for i in range(S):
            ref = stage(w[i], ref)

        got = pipeline_apply(stage, w, microbatch(x, M), mesh, axis="pipe")
        got = got.reshape(M * mb, dim)
        assert np.allclose(np.asarray(got), np.asarray(ref), atol=1e-5), \
            np.abs(np.asarray(got) - np.asarray(ref)).max()
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_pipeline_grads_flow_8dev():
    out = _run_subprocess("""
        from repro.dist.pipeline import microbatch, pipeline_apply
        S, M, mb, dim = 4, 4, 2, 8
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("pipe",))
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, dim, dim)) * 0.3
        x = jax.random.normal(jax.random.fold_in(key, 1), (M * mb, dim))

        def stage(p, h):
            return jnp.tanh(h @ p)

        def loss(w):
            y = pipeline_apply(stage, w, microbatch(x, M), mesh, "pipe")
            return jnp.sum(y ** 2)

        def loss_seq(w):
            h = x
            for i in range(S):
                h = stage(w[i], h)
            return jnp.sum(h ** 2)

        g_pipe = jax.grad(loss)(w)
        g_seq = jax.grad(loss_seq)(w)
        assert np.allclose(np.asarray(g_pipe), np.asarray(g_seq),
                           atol=1e-4), \
            np.abs(np.asarray(g_pipe) - np.asarray(g_seq)).max()
        print("PIPE_GRAD_OK")
    """)
    assert "PIPE_GRAD_OK" in out


def test_sharded_train_step_2x4_mesh():
    """A reduced arch's full train step under a (2,4) data x model mesh:
    the same code path the 512-device dry-run uses."""
    out = _run_subprocess("""
        from repro.configs import get_reduced
        from repro.launch import steps as S
        from repro.models import zoo
        from repro.dist.context import activation_sharding
        from repro.optim.optimizers import init_opt_state
        import numpy as np
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        cfg = get_reduced("yi_6b")
        fn = S.build_train_step(cfg)
        params = zoo.init_params(jax.random.PRNGKey(0), cfg)
        opt = init_opt_state(params, S.default_opt(cfg))
        batch = zoo.train_batch(cfg, 4, 16, jax.random.PRNGKey(1))
        specs = {"batch": batch, "step": jnp.int32(0)}
        in_sh, out_sh, _ = S.train_shardings(cfg, mesh, specs)
        with activation_sharding(mesh):
            step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            p2, o2, m = step(params, opt, batch, jnp.int32(0))
        assert np.isfinite(float(m["loss"]))
        print("TRAIN_STEP_OK", float(m["loss"]))
    """)
    assert "TRAIN_STEP_OK" in out


def test_compressed_allreduce_8dev():
    out = _run_subprocess("""
        from repro.dist.collectives import compressed_allreduce
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))

        def f(g, e):
            return compressed_allreduce(g[0], e[0], 0.5, "data")

        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        e = jnp.zeros((8, 64))
        synced, resid = jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(), P("data")), check_vma=False)(g, e)
        # error feedback: sparse + residual == original per shard
        print("COMPRESS_OK", float(jnp.abs(synced).sum()))
    """)
    assert "COMPRESS_OK" in out
