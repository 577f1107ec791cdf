"""Partition-local walk engine (ISSUE 3): slice/halo construction
round-trips, compacted-pool walks bit-identical to the replicated
reference at every shard count, packed-exchange accounting, overflow
spill/retry paths, per-shard balance stats, windowed ΔD gate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import incom
from repro.core.mpgp import mpgp_partition
from repro.core.shard_engine import (
    make_walk_mesh, partitioned_csr_for, run_walk_sharded,
)
from repro.core.termination import WalkCountController
from repro.core.transition import make_policy
from repro.core.walker import WalkSpec, run_walk_batch
from repro.graph.csr import build_partitioned_csr

SPEC = WalkSpec(max_len=40, min_len=8, mu=0.995, info_mode="incom",
                reg_start=16)


def _local(graph, part, k, n=96, seed=11, spec=SPEC, **kw):
    graph = graph.with_edge_cm()
    sources = jnp.arange(n, dtype=jnp.int32) % graph.num_nodes
    return run_walk_sharded(graph, sources, jax.random.PRNGKey(seed),
                            make_policy("huge"), spec,
                            jnp.asarray(part, jnp.int32), k,
                            engine="local", **kw)


def _parts(graph):
    p4 = mpgp_partition(graph, 4, gamma=2.0).assignment
    n = graph.num_nodes
    return {1: np.zeros(n, np.int64), 2: p4 % 2, 4: p4,
            8: np.arange(n) % 8}


# ---------------------------------------------------------------------------
# Partition-local storage: slice construction + halo round trips
# ---------------------------------------------------------------------------


def test_partitioned_csr_slices_match_global(medium_graph):
    """Every owned node's local CSR row is bit-for-bit its global row, and
    the edge-aligned halo metadata (owner, degree, Cm) matches the global
    arrays — phase A on the slice sees exactly what it saw globally."""
    g = medium_graph.with_edge_cm()
    asn = mpgp_partition(g, 4, gamma=2.0).assignment
    pcsr = build_partitioned_csr(g, asn, 4)
    gp = g.to_numpy()
    indptr = np.asarray(gp.indptr, np.int64)
    indices = np.asarray(gp.indices, np.int64)
    cm = np.asarray(gp.edge_cm, np.int64)
    deg = np.diff(indptr)
    local_of = np.asarray(pcsr.local_of)
    for s in range(4):
        sip = np.asarray(pcsr.slices.indptr[s])
        six = np.asarray(pcsr.slices.indices[s])
        sow = np.asarray(pcsr.slices.nbr_owner[s])
        sdeg = np.asarray(pcsr.slices.nbr_deg[s])
        scm = np.asarray(pcsr.slices.edge_cm[s])
        owned = np.where(asn == s)[0]
        assert pcsr.num_owned[s] == len(owned)
        for u in owned[:64]:
            lo, hi = sip[local_of[u]], sip[local_of[u] + 1]
            np.testing.assert_array_equal(six[lo:hi],
                                          indices[indptr[u]:indptr[u + 1]])
            np.testing.assert_array_equal(scm[lo:hi],
                                          cm[indptr[u]:indptr[u + 1]])
        valid = six >= 0
        np.testing.assert_array_equal(sow[valid], asn[six[valid]])
        np.testing.assert_array_equal(sdeg[valid], deg[six[valid]])
    # per-shard CSR bytes scale ~1/k: the slice is far below the global CSR
    full = (indptr.size + indices.size + cm.size) * 4
    assert pcsr.shard_csr_nbytes().max() < 0.55 * full


@pytest.mark.parametrize("num_parts", [1, 2, 3, 5])
def test_halo_remap_round_trip_random(num_parts):
    """Property-style round trip on random graphs/assignments: local row
    of owner(v) reproduces N(v); owned/local_of invert each other."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.graph.generators import rmat_graph

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def check(seed):
        rng = np.random.default_rng(seed)
        g = rmat_graph(64, 4, seed=seed % 97)
        asn = rng.integers(0, num_parts, g.num_nodes)
        pcsr = build_partitioned_csr(g, asn, num_parts)
        local_of = np.asarray(pcsr.local_of)
        gp = g.to_numpy()
        indptr = np.asarray(gp.indptr, np.int64)
        indices = np.asarray(gp.indices, np.int64)
        for v in rng.choice(g.num_nodes, size=8, replace=False):
            s = asn[v]
            assert pcsr.owned[s, local_of[v]] == v     # inverse maps agree
            sip = np.asarray(pcsr.slices.indptr[s])
            six = np.asarray(pcsr.slices.indices[s])
            lo, hi = sip[local_of[v]], sip[local_of[v] + 1]
            np.testing.assert_array_equal(
                six[lo:hi], indices[indptr[v]:indptr[v + 1]])

    check()


# ---------------------------------------------------------------------------
# Compacted engine: bit-identity vs the replicated k=1 reference
# ---------------------------------------------------------------------------


def test_local_engine_bit_identical_across_k(medium_graph):
    """Partition-local + compacted pools: walks, lengths and every InCoM
    moment are bit-identical across k in {1, 2, 4, 8} and match the dense
    k=1 reference walk-for-walk."""
    g = medium_graph.with_edge_cm()
    sources = jnp.arange(96, dtype=jnp.int32)
    key = jax.random.PRNGKey(11)
    dense = run_walk_batch(g, sources, key, make_policy("huge"), SPEC)
    runs = {k: _local(medium_graph, part, k) for k, part
            in _parts(medium_graph).items()}
    ref = runs[1]
    for k, st in runs.items():
        np.testing.assert_array_equal(np.asarray(ref.path),
                                      np.asarray(st.path), err_msg=f"k={k}")
        for f in ("H", "L", "EH", "EL", "EHL", "EH2", "EL2"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref.info, f)),
                np.asarray(getattr(st.info, f)), err_msg=f"k={k}.{f}")
    np.testing.assert_array_equal(np.asarray(dense.path),
                                  np.asarray(ref.path))
    np.testing.assert_array_equal(np.asarray(dense.info.L),
                                  np.asarray(ref.info.L))
    assert int(dense.accepts) == int(runs[4].accepts)
    assert int(dense.rejects) == int(runs[4].rejects)
    assert int(runs[4].msg_count) > 0


def test_local_matches_replicated_engine(medium_graph):
    """Same partition, both engines: identical walks and identical
    measured hand-off counts/bytes (the exchange inventory is an engine
    invariant, not an implementation detail)."""
    part = _parts(medium_graph)[4]
    st_l = _local(medium_graph, part, 4)
    g = medium_graph.with_edge_cm()
    st_r = run_walk_sharded(g, jnp.arange(96, dtype=jnp.int32),
                            jax.random.PRNGKey(11), make_policy("huge"),
                            SPEC, jnp.asarray(part, jnp.int32), 4,
                            engine="replicated")
    np.testing.assert_array_equal(np.asarray(st_l.path), np.asarray(st_r.path))
    np.testing.assert_array_equal(np.asarray(st_l.info.L),
                                  np.asarray(st_r.info.L))
    assert int(st_l.msg_count) == int(st_r.msg_count)
    assert float(st_l.msg_bytes) == float(st_r.msg_bytes)
    assert float(st_l.msg_bytes) == float(st_l.msg_bytes_analytic)
    assert float(st_l.msg_bytes) == incom.MSG_BYTES * int(st_l.msg_count)


def test_local_transports_identical(medium_graph):
    """gather-compacted broadcast, destination-bucketed all_to_all and the
    flat pool transport deliver identical walks and identical measured
    traffic (placement is deterministic in (source, record) order)."""
    part = _parts(medium_graph)[4]
    base = _local(medium_graph, part, 4, transport="pool")
    for tr, cap in (("gather", 16), ("a2a", 8)):
        st = _local(medium_graph, part, 4, transport=tr, exchange_cap=cap)
        np.testing.assert_array_equal(np.asarray(base.path),
                                      np.asarray(st.path), err_msg=tr)
        assert int(base.msg_count) == int(st.msg_count)
        assert float(base.msg_bytes) == float(st.msg_bytes)


def test_local_fullpath_and_window_modes(medium_graph):
    """The compacted engine keeps the baseline accountings: fullpath ships
    24+8L (measured == analytic) and reg_window ships 80+8K."""
    part = _parts(medium_graph)[4]
    spec_fp = WalkSpec(max_len=32, min_len=8, mu=-1.0, info_mode="fullpath",
                       reg_start=16)
    st = _local(medium_graph, part, 4, spec=spec_fp)
    assert int(st.msg_count) > 0
    assert float(st.msg_bytes) == pytest.approx(float(st.msg_bytes_analytic))
    spec_w = WalkSpec(max_len=32, min_len=8, mu=0.995, info_mode="incom",
                      reg_window=6)
    st = _local(medium_graph, part, 4, spec=spec_w)
    assert float(st.msg_bytes) == pytest.approx(
        (incom.MSG_BYTES + 8 * 6) * int(st.msg_count))


# ---------------------------------------------------------------------------
# Overflow paths: spill rounds (tiny exchange cap) + pool growth retry
# ---------------------------------------------------------------------------


def test_spill_rounds_with_tiny_exchange_cap(medium_graph):
    """cap=1 forces many spill rounds per superstep; the walk and the
    measured traffic must not change."""
    part = _parts(medium_graph)[4]
    ref = _local(medium_graph, part, 4)
    tiny = _local(medium_graph, part, 4, transport="gather", exchange_cap=1)
    np.testing.assert_array_equal(np.asarray(ref.path), np.asarray(tiny.path))
    assert int(ref.msg_count) == int(tiny.msg_count)
    assert float(ref.msg_bytes) == float(tiny.msg_bytes)


def test_pool_overflow_grows_and_recovers(medium_graph):
    """A deliberately undersized slot pool overflows, the driver doubles
    it and re-runs; the final walk is bit-identical and the retry is
    visible in the stats."""
    part = _parts(medium_graph)[4]
    ref = _local(medium_graph, part, 4)
    small, stats = _local(medium_graph, part, 4, pool_factor=0.05,
                          with_stats=True)
    np.testing.assert_array_equal(np.asarray(ref.path), np.asarray(small.path))
    assert stats["pool_retries"] >= 1
    assert stats["pool_slots"] > 0.05 * 96 / 4


def test_returning_walker_revives_ghost_slot():
    """Walkers that ping-pong between two shards every superstep must
    REVIVE their own ghost slots (no free slot exists at pool == B when
    every lane left a ghost behind); the walk still matches the dense
    reference and the driver never trips the pool == B overflow assert."""
    from repro.graph.csr import build_csr

    # 0-1, 2-3: two disjoint edges; partition splits every pair across
    # shards, so every accepted step is a migration straight back into
    # the slot the walker ghosted the superstep before.
    g = build_csr(np.array([[0, 1], [2, 3]]), num_nodes=4)
    part = np.array([0, 1, 0, 1])
    spec = WalkSpec(max_len=12, min_len=4, mu=-1.0, info_mode="incom",
                    reg_start=16)
    sources = jnp.arange(4, dtype=jnp.int32)
    key = jax.random.PRNGKey(3)
    dense = run_walk_batch(g, sources, key, make_policy("deepwalk"), spec)
    st = run_walk_sharded(g, sources, key, make_policy("deepwalk"), spec,
                          jnp.asarray(part, jnp.int32), 2, engine="local",
                          pool_factor=10.0)       # pool == B from the start
    np.testing.assert_array_equal(np.asarray(dense.path), np.asarray(st.path))
    np.testing.assert_array_equal(np.asarray(dense.info.L),
                                  np.asarray(st.info.L))
    # every step after the first is a hand-off for every live lane
    assert int(st.msg_count) >= 4 * (spec.max_len - 2)


def test_shard_stats_surface_balance(medium_graph):
    """with_stats exposes per-shard supersteps, occupancy and CSR bytes so
    balance skew is visible to benchmarks."""
    part = _parts(medium_graph)[4]
    st, stats = _local(medium_graph, part, 4, with_stats=True)
    for key in ("supersteps", "msg_count", "peak_lane_occupancy",
                "final_lane_occupancy", "owned_nodes",
                "csr_bytes_per_shard"):
        assert len(stats[key]) == 4, key
    assert max(stats["supersteps"]) == int(st.supersteps)
    assert sum(stats["owned_nodes"]) == medium_graph.num_nodes
    assert all(v <= stats["pool_slots"]
               for v in stats["peak_lane_occupancy"])


def test_local_spmd_matches_stacked(medium_graph):
    """shard_map execution of the partition-local engine (slices placed
    per device, all_to_all exchange) is walk-identical to the stacked
    emulation (broadcast exchange)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices (e.g. "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    mesh = make_walk_mesh(4)
    part = _parts(medium_graph)[4]
    g = medium_graph.with_edge_cm()
    sources = jnp.arange(64, dtype=jnp.int32)
    key = jax.random.PRNGKey(7)
    st_v = run_walk_sharded(g, sources, key, make_policy("huge"), SPEC,
                            jnp.asarray(part, jnp.int32), 4, engine="local")
    st_m = run_walk_sharded(g, sources, key, make_policy("huge"), SPEC,
                            jnp.asarray(part, jnp.int32), 4, mesh=mesh,
                            engine="local")
    np.testing.assert_array_equal(np.asarray(st_v.path), np.asarray(st_m.path))
    np.testing.assert_array_equal(np.asarray(st_v.info.L),
                                  np.asarray(st_m.info.L))
    assert int(st_v.msg_count) == int(st_m.msg_count)
    assert float(st_v.msg_bytes) == float(st_m.msg_bytes)


def test_partitioned_csr_cache_reuses(medium_graph):
    g = medium_graph.with_edge_cm()
    asn = _parts(medium_graph)[4]
    a = partitioned_csr_for(g, asn, 4)
    b = partitioned_csr_for(g, asn, 4)
    assert a is b


# ---------------------------------------------------------------------------
# ΔD controller noise floor (windowed gate)
# ---------------------------------------------------------------------------


def test_windowed_delta_gate_cuts_noise_floor():
    """A flat D series with pure sampling noise above the raw ΔD floor
    pins the paper-literal gate at max_rounds; the windowed-mean gate
    attenuates the noise ~window-fold and terminates."""
    # Alternating +-a sampling noise on a converged D: the raw delta is 2a
    # forever; the window-6 mean cancels it exactly once warm.
    series = 0.5 + 1e-3 * (-1.0) ** np.arange(64)
    raw = WalkCountController(delta=5e-4, min_rounds=2, max_rounds=40,
                              window=1)
    win = WalkCountController(delta=5e-4, min_rounds=2, max_rounds=40,
                              window=6)
    for d in series:
        if not raw.update_d(float(d)):
            break
    for d in series:
        if not win.update_d(float(d)):
            break
    assert raw.rounds == 40                  # noise keeps the raw gate open
    assert win.rounds < 15                   # smoothed delta crosses delta


def test_windowed_delta_gate_tracks_trend(small_graph):
    """On the seed graph at a tight delta the windowed gate must not stop
    EARLIER than the trend warrants: it ignores single-round noise
    downcrossings (the raw gate's false stops) yet still terminates
    before max_rounds."""
    from repro.core.corpus import generate_corpus

    kw = dict(policy="deepwalk",
              spec=WalkSpec(max_len=16, min_len=6, reg_start=16),
              delta=1e-4, min_rounds=2, max_rounds=30, seed=4)
    raw = generate_corpus(small_graph, window=1, **kw)
    win = generate_corpus(small_graph, window=3, **kw)
    assert win.rounds < 30                   # terminates despite noise
    assert win.rounds >= raw.rounds          # no noise-induced false stop