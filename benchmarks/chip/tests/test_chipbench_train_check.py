"""``correct`` for the training cell, at a size a CPU test run can hold:
the timed path as it is passes; with each fault the cell can have planted
under it, and with the lower-precision control in the program's place, it
does not.  The limits are the cell's own (``limits/<cell>.json``)."""

import time

import chipbench_cells
import jax
import pytest

from benchmarks.chip import harness


def limits(cell_name):
    return harness.load_json(harness.HERE / "limits" / f"{cell_name}.json")


@pytest.fixture
def tiny_train_cell():
    return chipbench_cells.tiny_train_cell()


SEED = 2 ** 33 + 5          # a seed wider than 32 bits


def _run(cell, tmp_path, fault=None):
    train = harness.runner(cell)
    return train.run(cell, SEED, 0.3, False, tmp_path, jax.devices("cpu"),
                     limits("train.opt-125m.s2048"), fault=fault)


def test_sound_run_is_correct(tiny_train_cell, tmp_path):
    out = _run(tiny_train_cell, tmp_path)
    assert out.correct, out.checks
    assert out.end_to_end["train_tokens_per_s"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(tiny_train_cell, tmp_path, fault):
    out = _run(tiny_train_cell, tmp_path, fault)
    assert not out.correct, out.checks


def test_control_is_not_correct(tiny_train_cell):
    """The float32 reference with its products rounded to float8, in the
    program's place, fails one of the numbers compared; the program's own
    reading on the same seed passes (both read by the runner's
    ``calibrate``, as the limits were)."""
    train = harness.runner(tiny_train_cell)
    lim = limits("train.opt-125m.s2048")
    got = {r["kind"]: r for r in train.calibrate(
        tiny_train_cell, [SEED], [SEED], [], jax.devices("cpu"))}
    assert set(got) == {"program", "control"}
    assert all(got["program"][k] <= lim[k] for k in lim), got["program"]
    assert any(got["control"][k] > lim[k] for k in lim), (got, lim)


def test_sound_run_is_correct_on_the_other_decoder_family(tmp_path):
    """RMSNorm, rotary positions, grouped-query attention and a SwiGLU
    MLP: the reference and the program's configuration agree there too."""
    out = _run(chipbench_cells.tiny_gqa_train_cell(), tmp_path)
    assert out.correct, out.checks


def test_stall_in_the_window_lowers_the_rate_and_is_found(tiny_train_cell):
    """A host stall inside the window counts against the rate, and the
    step that held it shows it under the phase it fell in."""
    train = harness.runner(tiny_train_cell)
    tr = train.Trainer(tiny_train_cell, SEED)
    tr.first_steps(1)
    calm = tr.window(1.0)
    feed = tr.data

    def stalled():
        for i, b in enumerate(feed):
            if i == 2:
                time.sleep(2.0)
            yield b

    tr.data = stalled()
    win = tr.window(1.0)
    assert win["tokens_per_s"] == pytest.approx(
        win["steps"] * tr.B * tr.S / win["window_s"])
    assert win["tokens_per_s"] < 0.6 * calm["tokens_per_s"], (win, calm)
    assert any(index == 2 and data_s >= 2.0
               for index, _, _, data_s, _ in win["slowest"]), win
