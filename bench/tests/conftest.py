import os
import sys

# The benchmark's tests run on the CPU at small sizes; the chip is only for
# bench/run.py.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import copy  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture()
def tiny_cell(tmp_path, monkeypatch):
    """A cell of BENCHMARK.json cut to a graph of 600 vertices, 4 lanes of
    2 walks, 3-lifetime chunks and 128-source walk batches, with its data
    cache under the test's temporary directory."""
    import common
    import graphs

    monkeypatch.setattr(graphs, "DATA_DIR", str(tmp_path / "data"))

    def make(name):
        cell = common.resolve(name)
        cfg = copy.deepcopy(cell.config)
        cfg["graph"].update(num_nodes=600, rmat_edges=1450, avg_degree=5)
        cfg["embed"].update(walker_batch=128, batch_groups=4, sync_period=3)
        cell.config = cfg
        cell.traffic = dict(cell.traffic, trace_seconds=1)
        return cell
    return make
