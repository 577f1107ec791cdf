"""TPU compiles at real size, without a chip.

The TPU compiler compiles for a described v5e:2x2 topology here, so what it
would refuse on the chip (Pallas lowering, tiling, VMEM, device memory)
fails these tests instead. Nothing runs: shapes go in, compiled programs
come out. Sizes are the yt-sim cell of ``chip_smoke.py`` at the paper's
settings (d=128, w=10, K=5, W=2, G=64, T=100, 50-lifetime chunks).

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dsgl import AliasTable, train_chunk
from repro.kernels.sgns import ops as sgns_ops
from repro.runtime import serve

N_YT = 1_138_499          # yt-sim |V|
DIM, WINDOW, NEG, W, G, T, CHUNK = 128, 10, 5, 2, 64, 100, 50
HBM_BYTES = 16 * 10**9    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_sgns_kernel_compiles_at_paper_width(spec):
    f = jax.jit(lambda c, o, n, v, lr: sgns_ops.sgns_lifetime_batch(
        c, o, n, v, lr, WINDOW, interpret=False))
    compiled = f.lower(spec((G, W, T, DIM)), spec((G, W, T, DIM)),
                       spec((G, T, NEG, DIM)), spec((G, W, T), jnp.bool_),
                       spec(())).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_train_chunk_compiles_at_yt_sim(spec, use_kernel, monkeypatch):
    # The kernel picks compiled-vs-interpreted from jax.default_backend(),
    # which is the CPU here; the described chip is what it compiles for.
    monkeypatch.setattr(sgns_ops, "on_tpu", lambda: True)
    phi = spec((1, N_YT, DIM))
    compiled = train_chunk.lower(
        phi, phi, spec((CHUNK, 1, G, W, T), jnp.int32),
        AliasTable(prob=spec((N_YT,)), alias=spec((N_YT,), jnp.int32)),
        spec((0,), jnp.int32), spec((2,), jnp.uint32), spec((CHUNK,)),
        WINDOW, NEG, use_kernel, False).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    assert _device_bytes(compiled) < HBM_BYTES


def test_serve_topk_wave_compiles_at_yt_sim(spec):
    """The top-K wave at ServeConfig(batch_slots=8): the (d, B, |V|) f32
    product it materialises is 4.7 GB and has to fit one chip. |V| padded
    to whole lanes makes the product row-major, so each plane the add
    chain reads is whole tiles; the chain reads them in place and holds no
    multiply to contract."""
    b, k = 8, 10
    n_pad = -(-N_YT // 128) * 128
    phi, phi_t = spec((N_YT, DIM)), spec((DIM, n_pad))
    u = spec((b,), jnp.int32)
    stages = [
        serve._all_products_jit.lower(phi, phi_t, u).compile(),
        serve._accumulate_jit.lower(spec((DIM, b, n_pad)), axis=0).compile(),
        serve._topk_from_scores_jit.lower(spec((b, n_pad)), u, k=k,
                                          n=N_YT).compile(),
    ]
    for compiled in stages:
        assert _device_bytes(compiled) < HBM_BYTES
    assert _device_bytes(stages[0]) >= b * n_pad * DIM * 4
    product = stages[0].as_text()
    assert f"f32[{DIM},{b},{n_pad}]{{2,1,0:" in product
    assert f"f32[{DIM},{n_pad}]{{0,1:" not in product    # no transpose
    assert "multiply" not in stages[1].as_text()
    assert stages[1].memory_analysis().temp_size_in_bytes <= b * n_pad * 4
    assert f"f32[{DIM},{n_pad}]{{1,0:" in \
        serve._phi_t_jit.lower(phi).compile().as_text()
