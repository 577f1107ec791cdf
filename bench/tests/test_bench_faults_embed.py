"""An embed run at a tiny size on the CPU, the chip check skipped: sound, it
is correct; with its timed path broken underneath, or with the bfloat16
reference in the program's place, it is not."""

import jax
import jax.numpy as jnp
import pytest

import control
import run

SEED = 2**31 + 17
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cell(cell):
    return run.run(cell, SEED, 1.0, False, None, PEAKS)


def failed(out):
    return sorted(k for k, c in out["checks"].items()
                  if not c["value"] <= c["limit"])


def test_sound_run_is_correct(tiny_cell):
    out = run_cell(tiny_cell("yt-sim.embed"))
    assert out["correct"], out["checks"]
    assert out["metrics"]["embed_walks_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_step_that_returns_its_state_unchanged(tiny_cell, monkeypatch):
    import repro.core.dsgl as dsgl
    real = dsgl.train_chunk

    def unchanged(phi_in, phi_out, *args):
        keep = (jnp.copy(phi_in), jnp.copy(phi_out))
        _, _, losses = real(phi_in, phi_out, *args)
        return keep[0], keep[1], losses

    monkeypatch.setattr(dsgl, "train_chunk", unchanged)
    out = run_cell(tiny_cell("yt-sim.embed"))
    assert not out["correct"]
    assert "change_gap" in failed(out)
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(tiny_cell, monkeypatch):
    import repro.core.dsgl as dsgl
    real = dsgl.train_chunk

    def half(phi_in, phi_out, walks, *args):
        g = walks.shape[2]
        return real(phi_in, phi_out, walks[:, :, :g // 2], *args)

    monkeypatch.setattr(dsgl, "train_chunk", half)
    out = run_cell(tiny_cell("yt-sim.embed"))
    assert not out["correct"]
    assert "loss_gap" in failed(out)


def test_walk_token_altered_where_produced(tiny_cell, monkeypatch):
    import repro.core.walker as walker
    real = walker.run_walk_batch

    def altered(*args, **kwargs):
        st = real(*args, **kwargs)
        # The third vertex repeats the second: a self-loop, never an arc.
        st.path = st.path.at[0, 2].set(st.path[0, 1])
        return st

    monkeypatch.setattr(walker, "run_walk_batch", altered)
    out = run_cell(tiny_cell("yt-sim.embed"))
    assert not out["correct"]
    assert "non_arcs" in failed(out)


def test_bfloat16_control_is_not_correct(tiny_cell):
    out = control.embed_control(tiny_cell("yt-sim.embed"), SEED)
    assert out["program"] == {"loss_gap": 0.0, "step1_change_gap": 0.0,
                              "change_gap": 0.0}
    assert out["control_correct"] is False
