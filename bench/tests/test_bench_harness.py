"""The harness works as data: every cell resolves its files by name, and a
cell, traffic mix and metric added as new files and entries are picked up
with no existing file edited."""

import hashlib
import json
import os
import shutil

import pytest

import common
import run

ROOT = common.ROOT


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("name", cells())
def test_every_cell_resolves(name):
    cell = common.resolve(name)
    driver = cell.driver()
    for fn in ("setup", "window", "release", "check"):
        assert callable(getattr(driver, fn))
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert {m["moves"] for m in cell.per_layer} <= names
    assert cell.config["name"] == cell.config_name


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_only_are_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns(".data", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = digest(root / "bench")

    (root / "bench" / "traffic" / "topk_k100.json").write_text(json.dumps({
        "driver": "reads_closed", "clients": 4, "k": 100, "zipf": 1.2,
        "batch_slots": 4, "check_sample": 8, "trace_seconds": 5}))
    (root / "bench" / "metrics" / "reads_per_wave.topk_k100.py").write_text(
        "def read(r):\n    return r.counts['reads'] / r.counts['waves']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "yt-sim.topk_k100", "config": "yt-sim",
        "traffic": "topk_k100", "chips": 1, "why": "wide top-K"})
    bench["end_to_end"][1]["workloads"].append("yt-sim.topk_k100")
    bench["per_layer"].append({
        "name": "reads_per_wave.topk_k100", "unit": "reads",
        "better": "higher", "source": "program_counter",
        "layer": "server scheduler", "moves": "topk_per_s",
        "workloads": ["yt-sim.topk_k100"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = common.resolve("yt-sim.topk_k100", str(root))
    assert cell.traffic["k"] == 100 and cell.config_name == "yt-sim"
    assert cell.driver_path.endswith("reads_closed.py")
    assert [m["name"] for m in cell.end_to_end] == ["topk_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["reads_per_wave.topk_k100"]
    reduced = {"window_s": 1.0, "busy_s": 0.5, "layers": {}, "modules": {}}
    got = run.per_layer_metrics(cell, reduced, {"reads": 12, "waves": 3},
                                {})
    assert got == {"reads_per_wave.topk_k100": {"value": 4.0,
                                                "unit": "reads"}}
    after = digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
