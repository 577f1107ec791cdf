"""Dynamic-graph subsystem tests: delta-CSR overlay, affected-vertex
detection from the corpus, vertex-keyed subset re-walks, cache
invalidation on mutation, and the end-to-end incremental refresh
acceptance criteria (<=30% re-walk, AUC within 0.02 of scratch,
bit-identical unaffected walks)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import EmbedConfig, embed_graph, refresh_embedding
from repro.core.incremental import affected_roots, changed_arc_codes
from repro.core.termination import WalkCountController
from repro.core.transition import make_policy
from repro.core.walker import WalkSpec, run_walk_batch
from repro.graph.csr import build_csr, edge_common_neighbors_ref
from repro.graph.delta import DeltaCSR, EdgeBatch, bump_graph_version, \
    graph_version
from repro.graph.generators import churn_batch, rmat_graph, undirected_edges


def _und(graph):
    return undirected_edges(graph)


# ---------------------------------------------------------------------------
# Delta overlay
# ---------------------------------------------------------------------------


class TestDeltaOverlay:
    def _base(self, n=48, m=160, seed=0):
        rng = np.random.default_rng(seed)
        edges = rng.integers(0, n, (m, 2))
        return build_csr(edges, n)

    def test_merge_equals_rebuild(self):
        g = self._base()
        und = _und(g)
        rng = np.random.default_rng(1)
        dele = und[rng.choice(len(und), 8, replace=False)]
        ins = np.stack([rng.integers(0, 48, 12), rng.integers(0, 48, 12)], 1)
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=ins, delete=dele))
        merged = d.graph().to_numpy()

        codes = und[:, 0] * 48 + und[:, 1]
        keep = ~np.isin(codes, dele[:, 0] * 48 + dele[:, 1])
        ins_f = ins[ins[:, 0] != ins[:, 1]]
        ref = build_csr(np.concatenate([und[keep], np.sort(ins_f, 1)]),
                        48).to_numpy()
        np.testing.assert_array_equal(np.asarray(merged.indptr),
                                      np.asarray(ref.indptr))
        np.testing.assert_array_equal(np.asarray(merged.indices),
                                      np.asarray(ref.indices))

    def test_rows_stay_sorted(self):
        g = self._base()
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=np.array([[0, 47], [0, 1], [3, 40]])))
        m = d.graph().to_numpy()
        indptr = np.asarray(m.indptr)
        indices = np.asarray(m.indices)
        for u in range(len(indptr) - 1):
            row = indices[indptr[u]:indptr[u + 1]]
            assert (np.diff(row) > 0).all(), f"row {u} not sorted/unique"

    def test_duplicate_insert_ignored(self):
        g = self._base()
        und = _und(g)
        before = g.num_edges
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=und[:3]))       # already present
        assert d.graph().num_edges == before

    def test_delete_then_insert_resurrects(self):
        g = self._base()
        e = _und(g)[:1]
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(delete=e))
        d.apply_batch(EdgeBatch(insert=e))
        np.testing.assert_array_equal(
            np.asarray(d.graph().to_numpy().indices),
            np.asarray(g.to_numpy().indices))

    def test_insert_grows_vertex_set(self):
        g = self._base(n=10, m=30)
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=np.array([[2, 14]])))
        m = d.graph()
        assert m.num_nodes == 15
        assert 14 in m.neighbors(2)

    def test_incremental_edge_cm_matches_full(self):
        g = self._base().with_edge_cm()
        und = _und(g)
        rng = np.random.default_rng(2)
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(
            insert=np.stack([rng.integers(0, 48, 6),
                             rng.integers(0, 48, 6)], 1),
            delete=und[rng.choice(len(und), 5, replace=False)]))
        merged = d.graph()
        np.testing.assert_array_equal(
            np.asarray(merged.to_numpy().edge_cm),
            edge_common_neighbors_ref(merged))

    def test_auto_compaction_threshold(self):
        g = self._base()
        d = DeltaCSR(g, compact_threshold=0.01)
        und = _und(g)
        d.apply_batch(EdgeBatch(delete=und[:10]))      # > 1% of arcs
        assert d.compactions == 1
        assert d.pending_arcs == 0

    def test_weighted_overlay(self):
        edges = np.array([[0, 1], [1, 2], [2, 3]])
        g = build_csr(edges, 4, weights=np.array([1.0, 2.0, 3.0],
                                                 np.float32))
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=np.array([[0, 3]]),
                                insert_weights=np.array([5.0])))
        m = d.graph().to_numpy()
        indptr = np.asarray(m.indptr)
        row0 = np.asarray(m.indices)[indptr[0]:indptr[1]]
        w0 = np.asarray(m.weights)[indptr[0]:indptr[1]]
        assert row0.tolist() == [1, 3]
        assert w0.tolist() == [1.0, 5.0]

    def test_out_of_range_delete_ignored_no_code_alias(self):
        """delete=[[0, n+k]] must be a no-op: 0*n + (n+k) aliases the
        arc code of a REAL edge, so unguarded encoding would tombstone
        an unrelated arc (one direction only)."""
        g = build_csr(np.array([[2, 3], [1, 4], [0, 2]]), 10)
        before = np.asarray(g.to_numpy().indices).copy()
        d = DeltaCSR(g, compact_threshold=0)
        # 0*10 + 23 == 23 == code of arc (2, 3)
        d.apply_batch(EdgeBatch(delete=np.array([[0, 23]])))
        m = d.graph().to_numpy()
        np.testing.assert_array_equal(np.asarray(m.indices), before)
        assert d.pending_arcs == 0

    def test_resurrected_edge_takes_new_weight(self):
        edges = np.array([[0, 1], [1, 2]])
        g = build_csr(edges, 3, weights=np.array([2.0, 3.0], np.float32))
        base_w = np.asarray(g.to_numpy().weights).copy()
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(delete=np.array([[0, 1]])))
        d.apply_batch(EdgeBatch(insert=np.array([[0, 1]]),
                                insert_weights=np.array([7.5])))
        m = d.graph().to_numpy()
        indptr = np.asarray(m.indptr)
        w01 = float(np.asarray(m.weights)[indptr[0]])
        assert w01 == 7.5                       # re-priced, not stale 2.0
        # and the caller's base graph was never mutated in place
        np.testing.assert_array_equal(np.asarray(g.to_numpy().weights),
                                      base_w)

    def test_version_bumps_on_mutation(self):
        g = self._base()
        d = DeltaCSR(g, compact_threshold=0)
        v1 = d.graph()
        assert graph_version(v1) == 0
        d.apply_batch(EdgeBatch(insert=np.array([[1, 2]])))
        # Retired view's version is bumped so (id, version) cache keys
        # can never serve its pre-mutation derivatives to a new view.
        assert graph_version(v1) > 0
        v2 = d.graph()
        assert v2 is not v1


# ---------------------------------------------------------------------------
# Vertex-keyed RNG: subset re-walks are bit-identical
# ---------------------------------------------------------------------------


class TestVertexKeyedRng:
    def _setup(self, small_graph):
        g = small_graph.with_edge_cm()
        spec = WalkSpec(max_len=24, min_len=6, mu=0.995, info_mode="incom",
                        reg_start=16, rng_mode="vertex")
        return g, make_policy("huge"), spec, jax.random.PRNGKey(11)

    def test_subset_matches_full_batch_dense(self, small_graph):
        g, policy, spec, key = self._setup(small_graph)
        full = run_walk_batch(g, jnp.arange(g.num_nodes, dtype=jnp.int32),
                              key, policy, spec)
        sub_ids = np.array([1, 7, 60, 130, 255], np.int32)
        sub = run_walk_batch(g, jnp.asarray(sub_ids), key, policy, spec)
        np.testing.assert_array_equal(np.asarray(full.path)[sub_ids],
                                      np.asarray(sub.path))
        np.testing.assert_array_equal(np.asarray(full.info.L)[sub_ids],
                                      np.asarray(sub.info.L))

    def test_subset_matches_full_batch_sharded(self, small_graph):
        g, policy, spec, key = self._setup(small_graph)
        part = jnp.asarray(np.arange(g.num_nodes) % 3, jnp.int32)
        full = run_walk_batch(g, jnp.arange(g.num_nodes, dtype=jnp.int32),
                              key, policy, spec, part, num_shards=3)
        sub_ids = np.array([0, 5, 77, 200], np.int32)
        sub = run_walk_batch(g, jnp.asarray(sub_ids), key, policy, spec,
                             part, num_shards=3)
        np.testing.assert_array_equal(np.asarray(full.path)[sub_ids],
                                      np.asarray(sub.path))

    def test_chunking_invariance(self, small_graph):
        """Splitting one source set into chunks under a shared key gives
        the same walks — the property the streaming pipeline relies on to
        re-walk arbitrary subsets without knowing chunk boundaries."""
        g, policy, spec, key = self._setup(small_graph)
        ids = np.arange(100, dtype=np.int32)
        whole = run_walk_batch(g, jnp.asarray(ids), key, policy, spec)
        parts = [run_walk_batch(g, jnp.asarray(ids[i:i + 32]), key, policy,
                                spec) for i in range(0, 100, 32)]
        stitched = np.concatenate([np.asarray(p.path) for p in parts])
        np.testing.assert_array_equal(np.asarray(whole.path), stitched)

    def test_lane_vs_vertex_keying_semantics(self, small_graph):
        """Duplicate sources separate the two modes: lane keying draws per
        BATCH POSITION (duplicate roots diverge), vertex keying draws per
        SOURCE VERTEX (duplicate roots walk identically)."""
        g = small_graph.with_edge_cm()
        hub = int(np.argmax(np.asarray(g.degrees())))
        ids = jnp.full((8,), hub, jnp.int32)
        key = jax.random.PRNGKey(11)
        policy = make_policy("huge")
        base = dict(max_len=24, min_len=6, mu=0.995, info_mode="incom",
                    reg_start=16)
        lane = run_walk_batch(g, ids, key, policy, WalkSpec(**base))
        vert = run_walk_batch(g, ids, key, policy,
                              WalkSpec(**base, rng_mode="vertex"))
        lane_paths = np.asarray(lane.path)
        vert_paths = np.asarray(vert.path)
        assert (vert_paths == vert_paths[0]).all(), \
            "vertex keying must give duplicate roots identical walks"
        assert (lane_paths != lane_paths[0]).any(), \
            "lane keying draws per position; duplicates should diverge"


# ---------------------------------------------------------------------------
# Affected-vertex detection (recovered from the corpus)
# ---------------------------------------------------------------------------


class TestAffectedDetection:
    def test_path_line_graph(self):
        # 0-1-2-3-4 path; walks recorded manually.
        g = build_csr(np.array([[0, 1], [1, 2], [2, 3], [3, 4]]), 5)
        walks = np.array([
            [0, 1, 2, -1],        # traverses (1,2)
            [2, 3, 4, -1],        # traverses (2,3), (3,4)
            [4, 3, -1, -1],       # traverses (3,4)
        ], np.int32)
        roots = np.array([0, 2, 4])
        changed = np.array([[1, 2]])
        aff = affected_roots(walks, roots, changed, np.array([1, 2]), 5)
        # endpoints 1,2 + root 0 (its walk traverses 1-2); root 2's walk
        # does NOT traverse 1-2 (it goes 2-3-4)
        assert aff.tolist() == [True, True, True, False, False]

    def test_reverse_direction_detected(self):
        g = build_csr(np.array([[0, 1], [1, 2]]), 3)
        walks = np.array([[2, 1, 0, -1]], np.int32)      # traverses 1-0
        aff = affected_roots(walks, np.array([2]), np.array([[0, 1]]),
                             np.array([0, 1]), 3)
        assert aff[2]

    def test_empty_churn(self):
        walks = np.array([[0, 1, -1]], np.int32)
        aff = affected_roots(walks, np.array([0]),
                             np.zeros((0, 2), np.int64),
                             np.zeros(0, np.int64), 3)
        assert not aff.any()

    def test_paranoid_superset_and_exactness(self, small_graph):
        """Paranoid mode must (a) contain the traversal set and (b) flag
        every walk whose from-scratch re-run on the mutated graph differs
        — the provable kept-walk invariance guarantee."""
        g = small_graph.with_edge_cm()
        n = g.num_nodes
        spec = WalkSpec(max_len=20, min_len=6, mu=0.995, info_mode="incom",
                        reg_start=16, rng_mode="vertex")
        policy = make_policy("huge")
        key = jax.random.PRNGKey(3)
        old = run_walk_batch(g, jnp.arange(n, dtype=jnp.int32), key,
                             policy, spec)
        walks_old = np.asarray(old.path)

        und = _und(g)
        rng = np.random.default_rng(5)
        dele = und[rng.choice(len(und), 4, replace=False)]
        ins = np.stack([rng.integers(0, n, 5), rng.integers(0, n, 5)], 1)
        d = DeltaCSR(g, compact_threshold=0)
        d.apply_batch(EdgeBatch(insert=ins, delete=dele))
        g2 = d.compact()
        changed = np.concatenate([ins, dele])
        touched = np.unique(changed)

        roots = np.arange(n)
        trav = affected_roots(walks_old, roots, changed, touched, n)
        par = affected_roots(walks_old, roots, changed, touched, n,
                             mode="paranoid", old_graph=g, new_graph=g2)
        assert (trav <= par).all()

        new = run_walk_batch(g2, jnp.arange(n, dtype=jnp.int32), key,
                             policy, spec)
        same = (walks_old == np.asarray(new.path)).all(axis=1)
        assert not (~same & ~par).any(), \
            "paranoid detector missed a diverging walk"

    def test_changed_arc_codes_sorted_both_dirs(self):
        codes = changed_arc_codes(np.array([[3, 1], [0, 2]]), 10)
        assert codes.tolist() == sorted(codes.tolist())
        assert set(codes.tolist()) == {31, 13, 2, 20}


# ---------------------------------------------------------------------------
# Cache invalidation on mutation (pcsr + slot pool)
# ---------------------------------------------------------------------------


class TestCacheInvalidation:
    def test_pcsr_never_stale_across_mutation(self, small_graph):
        from repro.core.shard_engine import partitioned_csr_for

        g = small_graph.with_edge_cm()
        n = g.num_nodes
        asn = np.arange(n) % 2
        d = DeltaCSR(g, compact_threshold=0)
        v1 = d.graph()
        p1 = partitioned_csr_for(v1, asn, 2)
        assert partitioned_csr_for(v1, asn, 2) is p1       # cache hit
        d.apply_batch(EdgeBatch(insert=np.array([[0, n - 1]])))
        v2 = d.graph()
        p2 = partitioned_csr_for(v2, asn, 2)
        assert p2 is not p1
        # the new pcsr must contain the inserted arc
        shard_of_0 = asn[0]
        row = np.asarray(p2.slices.indices[shard_of_0])
        indptr = np.asarray(p2.slices.indptr[shard_of_0])
        local0 = int(np.asarray(p2.local_of)[0])
        assert (n - 1) in row[indptr[local0]:indptr[local0 + 1]]

    def test_version_guard_defeats_id_aliasing(self, small_graph):
        """Even if a mutated graph were passed under the SAME object (the
        in-place overlay hazard the PR-3 cache could not see), the bumped
        version must miss the cache."""
        from repro.core.shard_engine import partitioned_csr_for

        g = small_graph.with_edge_cm()
        asn = np.arange(g.num_nodes) % 2
        p1 = partitioned_csr_for(g, asn, 2)
        bump_graph_version(g)          # simulate in-place mutation
        p2 = partitioned_csr_for(g, asn, 2)
        assert p2 is not p1

    def test_walks_see_mutation(self, small_graph):
        """run_walk_sharded on the post-mutation view must walk the NEW
        graph (no stale pcsr serving)."""
        from repro.core.shard_engine import run_walk_sharded

        g = small_graph.with_edge_cm()
        n = g.num_nodes
        spec = WalkSpec(max_len=16, min_len=4, mu=0.995, info_mode="incom",
                        reg_start=16, rng_mode="vertex")
        policy = make_policy("huge")
        part = jnp.asarray(np.arange(n) % 2, jnp.int32)
        key = jax.random.PRNGKey(0)
        src = jnp.arange(n, dtype=jnp.int32)

        d = DeltaCSR(g, compact_threshold=0)
        st1 = run_walk_sharded(d.graph(), src, key, policy, spec, part, 2,
                               engine="local")
        # delete EVERY edge of the highest-degree node; its walks must
        # become length-1 dead ends on the mutated graph
        hub = int(np.argmax(np.asarray(g.degrees())))
        nbrs = g.neighbors(hub)
        d.apply_batch(EdgeBatch(
            delete=np.stack([np.full(len(nbrs), hub), nbrs], 1)))
        st2 = run_walk_sharded(d.graph(), src, key, policy, spec, part, 2,
                               engine="local")
        assert float(np.asarray(st1.info.L)[hub]) > 1.0
        assert float(np.asarray(st2.info.L)[hub]) == 1.0


# ---------------------------------------------------------------------------
# Ring replacement + seeded gate
# ---------------------------------------------------------------------------


class TestRingReplace:
    def test_ocn_exact_after_replace(self):
        from repro.core.corpus import CorpusRing, ring_append, ring_replace

        ring = CorpusRing.create(8, 5, 10)
        w0 = jnp.asarray(np.array([[0, 1, 2, -1, -1],
                                   [3, 4, -1, -1, -1]], np.int32))
        ring = ring_append(ring, w0, jnp.asarray([3, 2], jnp.int32))
        w1 = jnp.asarray(np.array([[5, 6, 7, 8, -1]], np.int32))
        ring = ring_replace(ring, jnp.asarray([0], jnp.int32), w1,
                            jnp.asarray([4], jnp.int32))
        ocn = np.asarray(ring.ocn)
        expect = np.bincount([5, 6, 7, 8, 3, 4], minlength=10)
        np.testing.assert_array_equal(ocn, expect)
        assert int(ring.cursor) == 2                  # replace ≠ append
        assert int(ring.total) == 2

    def test_untouched_slots_bitwise_stable(self):
        from repro.core.corpus import CorpusRing, ring_append, ring_replace

        ring = CorpusRing.create(4, 3, 6)
        w = jnp.asarray(np.array([[0, 1, -1], [2, 3, -1], [4, 5, -1]],
                                 np.int32))
        ring = ring_append(ring, w, jnp.asarray([2, 2, 2], jnp.int32))
        before = np.asarray(ring.walks).copy()
        ring2 = ring_replace(ring, jnp.asarray([1], jnp.int32),
                             jnp.asarray([[5, 0, 1]], jnp.int32),
                             jnp.asarray([3], jnp.int32))
        after = np.asarray(ring2.walks)
        np.testing.assert_array_equal(before[[0, 2, 3]], after[[0, 2, 3]])


class TestSeededGate:
    def test_converged_history_no_extra_rounds(self):
        hist = [0.5, 0.41, 0.4, 0.4]
        gate = WalkCountController(delta=1e-2, min_rounds=1,
                                   max_rounds=len(hist) + 3,
                                   seed_history=hist)
        # refreshed D lands where the prior run converged -> stop at once
        assert gate.update_d(0.4005) is False

    def test_shifted_d_walks_more(self):
        hist = [0.5, 0.41, 0.4, 0.4]
        gate = WalkCountController(delta=1e-2, min_rounds=1,
                                   max_rounds=len(hist) + 3,
                                   seed_history=hist)
        assert gate.update_d(0.46) is True            # churn moved D
        assert gate.update_d(0.461) is False          # re-converged

    def test_seed_replays_windowed_smoothing(self):
        hist = [0.5, 0.4]
        gate = WalkCountController(delta=1e-3, window=2, seed_history=hist)
        ref = WalkCountController(delta=1e-3, window=2)
        ref.update_d(0.5)
        ref.update_d(0.4)
        assert gate._smooth == ref._smooth

    def test_no_min_rounds_burn_in(self):
        """Seeded gates judge the first post-churn D immediately (the
        cold-start path would force min_rounds extra walks)."""
        hist = [0.3] * 5
        gate = WalkCountController(delta=1e-2, min_rounds=1,
                                   max_rounds=10, seed_history=hist)
        assert gate.update_d(0.3001) is False


# ---------------------------------------------------------------------------
# churn generator
# ---------------------------------------------------------------------------


class TestChurnBatch:
    def test_shape_and_freshness(self, medium_graph):
        und = _und(medium_graph)
        batch = churn_batch(medium_graph, 0.05, seed=2)
        assert batch.num_changes >= int(0.04 * len(und))
        existing = set(map(tuple, np.sort(und, 1).tolist()))
        for e in np.sort(batch.insert, 1).tolist():
            assert tuple(e) not in existing
        for e in np.sort(batch.delete, 1).tolist():
            assert tuple(e) in existing

    def test_deterministic(self, medium_graph):
        a = churn_batch(medium_graph, 0.05, seed=2)
        b = churn_batch(medium_graph, 0.05, seed=2)
        np.testing.assert_array_equal(a.insert, b.insert)
        np.testing.assert_array_equal(a.delete, b.delete)


def test_refresh_extra_rounds_never_wrap_a_full_ring(small_graph):
    """When the corpus ring is exactly full, the ΔD top-up must stop
    instead of wrapping — a wrap would overwrite retained walks of
    UNAFFECTED roots and permanently over-count ocn."""
    from repro.core.api import make_walk_plan
    from repro.core.dsgl import DSGLConfig
    from repro.core.incremental import IncrementalRefresh
    from repro.runtime.trainer import StreamingEmbedPipeline

    cfg = EmbedConfig(dim=8, epochs=1, max_len=16, min_len=4, window=3,
                      negatives=2, rng_mode="vertex")
    policy, spec, _ = make_walk_plan(cfg)
    # Fixed 2-round run fills a 2-round ring to exactly its capacity.
    rounds = dict(delta=-1.0, min_rounds=2, max_rounds=2)
    dcfg = DSGLConfig(dim=8, window=3, negatives=2, seed=0)
    pipe = StreamingEmbedPipeline(small_graph.with_edge_cm(), policy, spec,
                                  rounds, dcfg)
    pipe.run()
    assert int(pipe.ring.total) == pipe.ring.capacity     # full

    walks_before = np.asarray(pipe.ring.walks).copy()
    roots_before = pipe._slot_root.copy()
    refresher = IncrementalRefresh(pipe)
    batch = churn_batch(small_graph, 0.05, seed=4)
    refresher.apply_updates(batch)
    stats = refresher.refresh(max_extra_rounds=4)
    assert stats.extra_rounds == 0                        # capacity guard
    # every slot rooted at an unaffected vertex is still bit-identical
    changed_edges = np.concatenate([batch.insert, batch.delete])
    aff = affected_roots(walks_before, roots_before, changed_edges,
                         np.unique(changed_edges),
                         small_graph.num_nodes)
    walks_after = np.asarray(pipe.ring.walks)
    kept = ~aff[np.maximum(roots_before, 0)] & (roots_before >= 0)
    np.testing.assert_array_equal(walks_before[kept], walks_after[kept])
    # and ocn stayed exact (recount over all slots)
    w = walks_after[roots_before >= 0]
    cnt = np.bincount(w[w >= 0], minlength=small_graph.num_nodes)
    np.testing.assert_array_equal(cnt, np.asarray(pipe.ring.ocn))


def test_refresh_detect_override_is_per_call(small_graph):
    """detect= in refresh_embedding applies to that call only; the
    refresher's configured mode is restored afterwards."""
    cfg = EmbedConfig(dim=8, epochs=1, max_len=16, min_len=4, window=3,
                      negatives=2, delta=1e-2)
    _, _, state = embed_graph(small_graph, cfg, num_shards=1,
                              return_state=True)
    assert state.refresher.detect == "traversal"
    batch = churn_batch(small_graph, 0.02, seed=5)
    refresh_embedding(state, batch, detect="paranoid",
                      fine_tune_steps=1, max_extra_rounds=0)
    assert state.refresher.detect == "traversal"


def test_refresh_rejects_vertex_growth_before_draining(small_graph):
    """Churn that grows |V| must be rejected BEFORE the churn log drains
    or the overlay compacts — a failed refresh leaves the refresher
    consistent instead of permanently corrupted."""
    cfg = EmbedConfig(dim=8, epochs=1, max_len=16, min_len=4, window=3,
                      negatives=2, delta=1e-2)
    _, _, state = embed_graph(small_graph, cfg, num_shards=1,
                              return_state=True)
    n = small_graph.num_nodes
    grow = EdgeBatch(insert=np.array([[0, n + 3]]))
    with pytest.raises(ValueError, match="vertex set"):
        refresh_embedding(state, grow)
    # the staged churn is still in the log (nothing was drained)
    ins, _ = state.refresher.delta.pending_changes()
    assert len(ins) == 1


# ---------------------------------------------------------------------------
# End-to-end acceptance (ISSUE 4)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_refresh_acceptance_e2e():
    """Mutate 5% of edges; the refresh must (a) re-walk <= 30% of
    vertices, (b) keep every walk rooted at an unaffected vertex
    bit-identical to its pre-update counterpart, and (c) land within
    0.02 AUC of a from-scratch recompute on the mutated graph."""
    from benchmarks.common import link_prediction_auc

    g = rmat_graph(2048, 10, seed=3)
    cfg = EmbedConfig(dim=32, epochs=1, lr=0.05, delta=1e-3, max_len=40,
                      min_len=10, window=6, negatives=4)
    phi0, _, state = embed_graph(g, cfg, num_shards=2, return_state=True)
    pipe = state.refresher.pipeline

    walks_before = np.asarray(pipe.ring.walks).copy()
    roots_before = pipe._slot_root.copy()
    batch = churn_batch(g, 0.05, seed=1)
    und = _und(g)
    assert batch.num_changes >= int(0.045 * len(und))   # really ~5% churn

    phi1, _, stats = refresh_embedding(state, batch)

    # (a) affected fraction
    assert stats.affected_frac <= 0.30, stats.affected_frac

    # (b) unaffected slots bit-identical: every slot whose pre-update
    # root is NOT affected must hold exactly its pre-update walk.
    walks_after = np.asarray(pipe.ring.walks)
    changed_slot = (walks_before != walks_after).any(axis=1)
    prev_written = roots_before >= 0
    # In-place changes split into REPLACED slots (must be affected-rooted)
    # and fresh APPENDS from extra rounds (previously unwritten slots).
    replaced_roots = roots_before[changed_slot & prev_written]
    assert len(set(replaced_roots.tolist())) <= stats.affected
    # every slot whose pre-update root was NOT replaced is bit-identical
    kept = ~changed_slot & prev_written
    assert kept.sum() > 0
    np.testing.assert_array_equal(walks_before[kept], walks_after[kept])
    # and specifically: recompute the affected set independently from the
    # pre-update corpus; no slot rooted OUTSIDE it may have changed.
    changed_edges = np.concatenate([batch.insert, batch.delete])
    aff_mask = affected_roots(
        walks_before[prev_written], roots_before[prev_written],
        changed_edges, np.unique(changed_edges), g.num_nodes)
    assert int(aff_mask.sum()) == stats.affected
    assert set(replaced_roots.tolist()) <= set(np.nonzero(aff_mask)[0]
                                               .tolist())
    unaffected_slot = prev_written & ~aff_mask[np.maximum(roots_before, 0)]
    np.testing.assert_array_equal(walks_before[unaffected_slot],
                                  walks_after[unaffected_slot])

    # (c) AUC parity with scratch recompute on the mutated graph
    g2 = state.graph
    cfg_s = dataclasses.replace(cfg, rng_mode="vertex")
    phi_scratch, _ = embed_graph(g2, cfg_s, num_shards=2)
    auc_refresh = link_prediction_auc(g2, phi1, np.random.default_rng(7))
    auc_scratch = link_prediction_auc(g2, phi_scratch,
                                      np.random.default_rng(7))
    assert abs(auc_refresh - auc_scratch) <= 0.02, \
        (auc_refresh, auc_scratch)
    # absolute sanity: the refreshed embedding still separates edges
    assert auc_refresh > 0.8, auc_refresh
