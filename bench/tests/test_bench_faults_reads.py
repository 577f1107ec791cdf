"""Read runs at a tiny size on the CPU, the chip check skipped: sound, they
are correct; with an answer altered where it is produced, or with the
bfloat16 reference in the program's place, they are not."""

import pytest

import control
import run

SEED = 2**31 + 23


def run_cell(cell):
    return run.run(cell, SEED, 0.5, False, None, None)


@pytest.mark.parametrize("name", ["yt-sim.topk", "lj-sim.pairs"])
def test_sound_run_is_correct(tiny_cell, name):
    out = run_cell(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_topk_answer_altered_where_produced(tiny_cell, monkeypatch):
    import repro.runtime.serve as serve
    real = serve._topk

    def altered(phi, u, k):
        vals, ids = real(phi, u, k)
        return vals.at[:, 0].add(1e-3), ids

    monkeypatch.setattr(serve, "_topk", altered)
    out = run_cell(tiny_cell("yt-sim.topk"))
    assert not out["correct"]
    assert out["checks"]["topk_score_mismatch"]["value"] > 0


def test_pair_answer_altered_where_produced(tiny_cell, monkeypatch):
    import repro.runtime.serve as serve
    real = serve._score_candidates

    def altered(phi, u, cand):
        return real(phi, u, cand).at[:, 0].add(1e-3)

    monkeypatch.setattr(serve, "_score_candidates", altered)
    out = run_cell(tiny_cell("lj-sim.pairs"))
    assert not out["correct"]
    assert out["checks"]["pair_score_mismatch"]["value"] > 0


@pytest.mark.parametrize("name", ["yt-sim.topk", "lj-sim.pairs"])
def test_bfloat16_control_is_not_correct(tiny_cell, name):
    assert control.reads_control(tiny_cell(name), SEED)[
        "control_correct"] is False
