"""graph/io.py coverage: weighted edge lists, comments/blank lines, npz,
and save -> load -> save round-trips on a delta-compacted graph; plus the
common-neighbor precompute (``edge_common_neighbors``) against its per-arc
reference."""

import numpy as np
import pytest

from repro.graph.csr import build_csr, edge_common_neighbors, \
    edge_common_neighbors_ref
from repro.graph.delta import DeltaCSR, EdgeBatch
from repro.graph.io import load_edge_list, save_edge_list


def _arrays(g):
    gn = g.to_numpy()
    return (np.asarray(gn.indptr), np.asarray(gn.indices),
            None if gn.weights is None else np.asarray(gn.weights))


def test_text_comments_and_blank_lines(tmp_path):
    p = tmp_path / "g.txt"
    p.write_text(
        "# a comment line\n"
        "\n"
        "0 1\n"
        "   \n"
        "1 2\n"
        "# trailing comment\n"
        "2 3\n")
    g = load_edge_list(str(p))
    assert g.num_nodes == 4
    assert g.num_edges == 6            # 3 undirected edges, both arcs
    np.testing.assert_array_equal(g.neighbors(1), [0, 2])


def test_weighted_text_round_trip(tmp_path):
    edges = np.array([[0, 1], [1, 2], [0, 3], [2, 3]])
    w = np.array([0.5, 2.0, 1.25, 4.0], np.float32)
    g = build_csr(edges, 4, weights=w)
    p = tmp_path / "w.txt"
    save_edge_list(g, str(p))
    g2 = load_edge_list(str(p))
    i1, x1, w1 = _arrays(g)
    i2, x2, w2 = _arrays(g2)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(x1, x2)
    assert w1 is not None and w2 is not None
    np.testing.assert_allclose(w1, w2)


def test_weighted_text_parse(tmp_path):
    p = tmp_path / "w.txt"
    p.write_text("0 1 2.5\n1 2 0.75\n")
    g = load_edge_list(str(p))
    assert g.weights is not None
    lo = int(np.asarray(g.indptr)[0])
    assert float(np.asarray(g.weights)[lo]) == 2.5


def test_npz_round_trip(tmp_path):
    edges = np.array([[0, 1], [1, 2], [3, 0]])
    g = build_csr(edges, 5)                      # isolated node 4
    p = tmp_path / "g.npz"
    save_edge_list(g, str(p))
    g2 = load_edge_list(str(p))
    assert g2.num_nodes == 5
    i1, x1, _ = _arrays(g)
    i2, x2, _ = _arrays(g2)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(x1, x2)


@pytest.mark.parametrize("fmt", ["txt", "npz"])
def test_delta_compacted_save_load_save_round_trip(tmp_path, fmt):
    """A graph mutated through the delta overlay and compacted back into
    CSR must survive save -> load -> save with identical bytes."""
    rng = np.random.default_rng(3)
    edges = rng.integers(0, 40, (120, 2))
    g = build_csr(edges, 40)
    d = DeltaCSR(g, compact_threshold=0)
    und_src = np.repeat(np.arange(40), np.diff(np.asarray(
        g.to_numpy().indptr)))
    arcs = np.stack([und_src, np.asarray(g.to_numpy().indices)], 1)
    und = arcs[arcs[:, 0] < arcs[:, 1]]
    d.apply_batch(EdgeBatch(
        insert=np.array([[0, 39], [5, 31], [7, 11]]),
        delete=und[:4]))
    compacted = d.compact()

    p1 = tmp_path / f"a.{fmt}"
    p2 = tmp_path / f"b.{fmt}"
    save_edge_list(compacted, str(p1))
    loaded = load_edge_list(str(p1), num_nodes=compacted.num_nodes)
    i1, x1, _ = _arrays(compacted)
    i2, x2, _ = _arrays(loaded)
    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(x1, x2)
    save_edge_list(loaded, str(p2))
    if fmt == "txt":
        assert p1.read_text() == p2.read_text()
    else:
        a, b = np.load(str(p1)), np.load(str(p2))
        np.testing.assert_array_equal(a["edges"], b["edges"])
        assert int(a["num_nodes"]) == int(b["num_nodes"])


@pytest.mark.parametrize("kind", ["rmat", "rmat_isolated", "ba", "directed"])
def test_edge_common_neighbors_matches_per_arc_reference(kind):
    """The wedge-enumeration Cm (symmetric CSRs) and the per-arc path
    (directed CSRs) give exactly the per-arc intersection counts."""
    from repro.graph.generators import barabasi_albert_graph, rmat_edges
    if kind == "rmat":
        g = build_csr(rmat_edges(2000, 12000, seed=1), 2000)
    elif kind == "rmat_isolated":        # many degree-0 rows, like yt-sim
        g = build_csr(rmat_edges(3000, 4000, seed=2), 3000)
    elif kind == "ba":
        g = barabasi_albert_graph(400, 5, seed=3)
    else:
        g = build_csr(rmat_edges(300, 1500, seed=4), 300, undirected=False)
    got = edge_common_neighbors(g)
    np.testing.assert_array_equal(got, edge_common_neighbors_ref(g))
    assert got.dtype == np.int32 and got.sum() > 0
