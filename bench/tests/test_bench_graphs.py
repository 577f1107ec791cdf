"""The deployments' graphs: seeded, no isolated vertex, degree on target,
and a data cache keyed by the graph's configuration."""

import os

import numpy as np
import pytest

import graphs

CFG = {"generator": "rmat", "num_nodes": 3000, "avg_degree": 5,
       "rmat_edges": 6400, "initiator": [0.57, 0.19, 0.19, 0.05],
       "dataset_seed": 3}


def test_deterministic_from_the_dataset_seed():
    a, b = graphs.edges_for(CFG), graphs.edges_for(dict(CFG))
    assert np.array_equal(a, b)
    c = graphs.edges_for(dict(CFG, dataset_seed=4))
    assert not np.array_equal(a, c)


def test_no_isolated_vertex_and_degree_on_target():
    arrays = graphs.build(CFG)
    deg = np.diff(arrays["indptr"])
    assert deg.min() >= 1
    assert abs(deg.mean() - CFG["avg_degree"]) <= 0.1 * CFG["avg_degree"]
    # The program's CSR and this module's arc keys hold the same arcs.
    n = CFG["num_nodes"]
    src = np.repeat(np.arange(n), deg)
    assert np.array_equal(src * n + arrays["indices"], arrays["arc_keys"])
    with pytest.raises(ValueError, match="not within 10%"):
        graphs.build(dict(CFG, rmat_edges=3000))
    with pytest.raises(ValueError, match="unknown graph generator"):
        graphs.edges_for(dict(CFG, generator="kronecker"))


def test_cache_is_keyed_and_a_stale_key_is_rebuilt(tmp_path):
    data = str(tmp_path)
    first = graphs.load("g", CFG, data)
    assert first["cached"] is False
    again = graphs.load("g", CFG, data)
    assert again["cached"] is True
    assert np.array_equal(first["edge_cm"], again["edge_cm"])
    other = graphs.load("h", CFG, data)          # another configuration
    assert other["cached"] is False
    changed = dict(CFG, dataset_seed=9)
    fresh = graphs.load("g", changed, data)
    assert fresh["cached"] is False
    names = sorted(os.listdir(data))
    assert names == sorted([f"g-{graphs.cache_key(changed)}",
                            f"h-{graphs.cache_key(CFG)}"])
