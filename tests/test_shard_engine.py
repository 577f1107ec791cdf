"""Partition-sharded BSP walk engine + streaming corpus ring (ISSUE 2):
shard-count invariance, measured hand-off traffic, ring/stream pipeline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import incom
from repro.core.corpus import CorpusRing, count_occurrences, ring_append, ring_to_numpy
from repro.core.mpgp import mpgp_partition
from repro.core.shard_engine import make_walk_mesh, run_walk_sharded
from repro.core.transition import make_policy
from repro.core.walker import WalkSpec, run_walk_batch, walks_to_numpy


def _sharded(graph, spec, part, k, n=96, seed=11, policy="huge"):
    graph = graph.with_edge_cm()
    sources = jnp.arange(n, dtype=jnp.int32) % graph.num_nodes
    return run_walk_sharded(graph, sources, jax.random.PRNGKey(seed),
                            make_policy(policy), spec,
                            jnp.asarray(part, jnp.int32), k)


def test_shard_count_invariance_bit_identical(medium_graph):
    """Same seed => bit-identical walks (paths, lengths, every InCoM
    moment) at 1 vs 2 vs 4 shards — the walk is a property of the graph
    and the RNG, never of the layout."""
    spec = WalkSpec(max_len=40, min_len=8, mu=0.995, info_mode="incom",
                    reg_start=16)
    part4 = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    st1 = _sharded(medium_graph, spec, np.zeros(medium_graph.num_nodes), 1)
    st2 = _sharded(medium_graph, spec, part4 % 2, 2)
    st4 = _sharded(medium_graph, spec, part4, 4)
    for other in (st2, st4):
        np.testing.assert_array_equal(np.asarray(st1.path),
                                      np.asarray(other.path))
        for f in ("H", "L", "EH", "EL", "EHL", "EH2", "EL2"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st1.info, f)),
                np.asarray(getattr(other.info, f)), err_msg=f)
    assert int(st1.msg_count) == 0
    assert int(st4.msg_count) > 0


def test_dense_engine_matches_sharded(medium_graph):
    """run_walk_batch without a partition (dense single-shard program)
    walks the identical chain as the k-shard BSP engine."""
    spec = WalkSpec(max_len=32, min_len=8, mu=0.995, info_mode="incom",
                    reg_start=16)
    part = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    g = medium_graph.with_edge_cm()
    sources = jnp.arange(96, dtype=jnp.int32)
    key = jax.random.PRNGKey(3)
    st_dense = run_walk_batch(g, sources, key, make_policy("huge"), spec)
    st_shard = run_walk_batch(g, sources, key, make_policy("huge"), spec,
                              jnp.asarray(part))
    p1, l1 = walks_to_numpy(st_dense)
    p2, l2 = walks_to_numpy(st_shard)
    np.testing.assert_array_equal(l1, l2)
    np.testing.assert_array_equal(p1, p2)
    assert int(st_dense.accepts) == int(st_shard.accepts)
    assert int(st_dense.rejects) == int(st_shard.rejects)


def test_measured_handoff_bytes_incom(medium_graph):
    """Every measured InCoM hand-off is exactly the Example-1 80-byte
    message, and the measured total equals the analytic closed form."""
    spec = WalkSpec(max_len=40, min_len=8, mu=0.995, info_mode="incom",
                    reg_start=16)
    part = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    st = _sharded(medium_graph, spec, part, 4, n=128)
    count = int(st.msg_count)
    assert count > 0
    assert float(st.msg_bytes) == pytest.approx(incom.MSG_BYTES * count)
    assert float(st.msg_bytes) == pytest.approx(float(st.msg_bytes_analytic))


def test_measured_handoff_bytes_fullpath(medium_graph):
    """Full-path hand-offs measure 24 + 8L from the routed path payload
    and match the analytic per-crossing sum exactly."""
    spec = WalkSpec(max_len=32, min_len=8, mu=-1.0, info_mode="fullpath",
                    reg_start=16)
    part = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    st = _sharded(medium_graph, spec, part, 4, n=96)
    count = int(st.msg_count)
    assert count > 0
    meas, analytic = float(st.msg_bytes), float(st.msg_bytes_analytic)
    assert meas == pytest.approx(analytic)
    per = meas / count
    # every message is 24 + 8L for some 2 <= L <= max_len
    assert 24 + 8 * 2 <= per <= 24 + 8 * spec.max_len
    assert (meas - 24.0 * count) % 8.0 == pytest.approx(0.0)


def test_windowed_message_carries_ring(medium_graph):
    """reg_window mode ships the K-entry H ring: 80 + 8K bytes/message."""
    k_win = 6
    spec = WalkSpec(max_len=32, min_len=8, mu=0.995, info_mode="incom",
                    reg_window=k_win)
    part = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    st = _sharded(medium_graph, spec, part, 4, n=96)
    count = int(st.msg_count)
    assert count > 0
    assert float(st.msg_bytes) == pytest.approx(
        (incom.MSG_BYTES + 8 * k_win) * count)


def test_spmd_shard_map_matches_stacked(medium_graph):
    """The shard_map execution (real per-device collectives) is
    bit-identical to the stacked vmap emulation."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (e.g. "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    mesh = make_walk_mesh(4)
    spec = WalkSpec(max_len=32, min_len=8, mu=0.995, info_mode="incom",
                    reg_start=16)
    part = mpgp_partition(medium_graph, 4, gamma=2.0).assignment
    g = medium_graph.with_edge_cm()
    sources = jnp.arange(64, dtype=jnp.int32)
    key = jax.random.PRNGKey(7)
    st_v = run_walk_sharded(g, sources, key, make_policy("huge"), spec,
                            jnp.asarray(part, jnp.int32), 4)
    st_m = run_walk_sharded(g, sources, key, make_policy("huge"), spec,
                            jnp.asarray(part, jnp.int32), 4, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(st_v.path), np.asarray(st_m.path))
    np.testing.assert_array_equal(np.asarray(st_v.info.L),
                                  np.asarray(st_m.info.L))
    assert int(st_v.msg_count) == int(st_m.msg_count)
    assert float(st_v.msg_bytes) == float(st_m.msg_bytes)


def test_walk_mesh_never_falls_back_silently(small_graph):
    """Asking for more devices than the host has raises instead of handing
    back None (which callers would run as the stacked emulation), and a
    mesh whose size differs from the shard count is refused."""
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"this host has {have}"):
        make_walk_mesh(have + 1)
    mesh = make_walk_mesh(1)
    spec = WalkSpec(max_len=16, min_len=4, mu=0.995, info_mode="incom")
    with pytest.raises(ValueError, match="for 2 shards"):
        run_walk_sharded(small_graph, jnp.arange(4, dtype=jnp.int32),
                         jax.random.PRNGKey(0), make_policy("deepwalk"),
                         spec, jnp.zeros(small_graph.num_nodes, jnp.int32),
                         2, mesh=mesh)


def test_corpus_ring_append_and_ocn(small_graph):
    """Ring slots, lengths and the fused ocn scatter-add match the host
    reference; wrap-around retires the oldest slots."""
    n = small_graph.num_nodes
    ring = CorpusRing.create(capacity=8, max_len=5, num_nodes=n)
    paths1 = jnp.asarray([[1, 2, 1, -1, -1], [3, 4, -1, -1, -1]], jnp.int32)
    lens1 = jnp.asarray([3, 2], jnp.int32)
    ring = ring_append(ring, paths1, lens1)
    walks, lengths = ring_to_numpy(ring)
    np.testing.assert_array_equal(walks, np.asarray(paths1))
    np.testing.assert_array_equal(lengths, [3, 2])
    ref = count_occurrences(np.asarray(paths1), np.asarray(lens1, np.int64), n)
    np.testing.assert_array_equal(np.asarray(ring.ocn), ref)
    # wrap: append 8 more rows into capacity-8 ring => first batch retired
    big = jnp.tile(jnp.asarray([[5, 6, -1, -1, -1]], jnp.int32), (8, 1))
    ring = ring_append(ring, big, jnp.full((8,), 2, jnp.int32))
    walks, lengths = ring_to_numpy(ring)
    assert walks.shape[0] == 8
    assert (walks[:, 0] == 5).all()
    assert int(ring.total) == 10


def test_generate_corpus_shim_matches_ring_and_controller(small_graph):
    """The compatibility shim still honors the Eq. 7 controller and its
    occurrence counts equal a host recount of the returned walks."""
    from repro.core.corpus import generate_corpus
    corpus = generate_corpus(
        small_graph, policy="deepwalk",
        spec=WalkSpec(max_len=16, min_len=6, reg_start=16),
        delta=1e-2, min_rounds=2, max_rounds=5, seed=4)
    assert 2 <= corpus.rounds <= 5
    assert len(corpus.stats["d_history"]) == corpus.rounds
    assert corpus.num_walks == corpus.rounds * small_graph.num_nodes
    ref = count_occurrences(corpus.walks, corpus.lengths,
                            small_graph.num_nodes)
    np.testing.assert_array_equal(corpus.ocn, ref)


def test_generate_corpus_host_spill_matches_ring(small_graph):
    """When full retention would overflow the device ring budget, the shim
    spills rounds to host and produces the identical corpus."""
    from repro.core.corpus import generate_corpus
    kw = dict(policy="deepwalk",
              spec=WalkSpec(max_len=16, min_len=6, reg_start=16),
              delta=1e-2, min_rounds=2, seed=4)
    dev = generate_corpus(small_graph, max_rounds=5, **kw)
    # max_rounds large enough that capacity * max_len >= 2**31 forces the
    # host path; the controller still stops at the same Delta-D round.
    host = generate_corpus(small_graph, max_rounds=2_000_000, **kw)
    assert host.rounds == dev.rounds
    np.testing.assert_array_equal(host.walks, dev.walks)
    np.testing.assert_array_equal(host.ocn, dev.ocn)


def test_streaming_pipeline_walks_are_edges_and_phi_finite(small_graph):
    """End-to-end streamed walk→train: ring walks are real graph walks and
    the node-space embeddings come back finite."""
    from repro.core.api import EmbedConfig, make_walk_plan
    from repro.core.dsgl import DSGLConfig
    from repro.runtime.trainer import StreamingEmbedPipeline

    cfg = EmbedConfig(dim=8, epochs=1, max_len=16, min_len=6)
    policy, spec, rounds = make_walk_plan(cfg)
    rounds["max_rounds"] = 3
    # round-robin partition: MPGP on this graph reaches locality 1.0
    # (zero crossings), which would make the hand-off assertion vacuous
    part = np.arange(small_graph.num_nodes, dtype=np.int32) % 2
    pipe = StreamingEmbedPipeline(
        small_graph, policy, spec, rounds,
        DSGLConfig(dim=8, window=4, negatives=3, seed=0),
        num_shards=2, assignment=part)
    out = pipe.run()
    phi = np.asarray(out["phi_in"])
    assert phi.shape == (small_graph.num_nodes, 8)
    assert np.isfinite(phi).all()
    assert out["steps"] == pipe.total_steps          # schedule completed
    assert out["stats"]["msg_count"] > 0             # real hand-offs happened

    corpus = pipe.corpus()
    indptr = np.asarray(small_graph.indptr)
    indices = np.asarray(small_graph.indices)
    for row, ln in zip(corpus.walks[:64], corpus.lengths[:64]):
        for a, b in zip(row[: ln - 1], row[1:ln]):
            assert b in indices[indptr[a]: indptr[a + 1]], (a, b)


def test_ring_chunk_indices_cover_pool():
    from repro.data.pipeline import ring_chunk_indices
    idx = ring_chunk_indices(jax.random.PRNGKey(0), base=10, pool=64,
                             count=2, shards=2, groups=4, windows=2)
    assert idx.shape == (2, 2, 4, 2)
    flat = np.asarray(idx).reshape(-1)
    assert flat.min() >= 10 and flat.max() < 74
    assert len(np.unique(flat)) == flat.size        # without replacement
    # tiny pool: tiling keeps shapes legal
    idx2 = ring_chunk_indices(jax.random.PRNGKey(1), base=0, pool=4,
                              count=2, shards=1, groups=4, windows=2)
    assert idx2.shape == (2, 1, 4, 2)
    assert np.asarray(idx2).max() < 4
