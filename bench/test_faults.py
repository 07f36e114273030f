"""Faults planted under the timed path must turn ``correct`` false.

Run by path (not part of the repository's test suite), on the CPU:
    python -m pytest bench/test_faults.py

Each case skips the harness's look for a chip and drives the rest of a run
of a cell (set-up, first steps, a short window, the comparison with the
plain reference) at the configuration's own widths, with the timed path
broken underneath:

  * training: a step that returns its state unchanged; the loss of half of
    the batch, the mean taken over the rest;
  * serving: an answer altered where the program produces it.

A sound run of each cell comes out correct under the same limits.
"""

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(BENCH / "drivers")]

import harness  # noqa: E402

SEED = 2_718_281_828


def run_cell(cell: str, seconds: float = 0.3, **hooks) -> dict:
    import jax
    files = harness.cell_files(cell)
    mix = dict(files["traffic"])
    driver = harness.load_module(BENCH / "drivers" /
                                 f"{mix['generator']}.py")
    if mix["generator"] == "open_loop":
        mix["rate_rps"] = 20.0
    return driver.run(files["config"], mix, files["checks"], SEED, seconds,
                      False, jax.devices()[:1], time.perf_counter(),
                      driver.Hooks(**hooks))


def correct(res: dict) -> bool:
    return all(c["ok"] for c in res["checks"].values()) \
        and res["failed"] == 0


def unchanged_state(step):
    import jax
    import jax.numpy as jnp

    def wrapped(params, aux, xt, tb, lr):
        _, aux, loss = step(jax.tree.map(jnp.copy, params), aux, xt, tb, lr)
        return params, aux, loss
    return wrapped


def half_batch_loss(model, sp, xt, noise, term_batches=None):
    from repro.core import pinn
    return pinn.residual_losses_stacked(model, sp, xt[:xt.shape[0] // 2],
                                        noise, term_batches=term_batches)


def altered_answer(exe):
    """Shifts the first answer of every slot of the pool by 1e-3."""
    mix = harness.cell_files("tonn-hjb20d.serve-80")["traffic"]
    return lambda x: exe(x).at[::mix["slot_points"]].add(1e-3)


@pytest.mark.parametrize("cell", ["tonn-hjb20d.train", "tonn-hjb20d.serve-80"])
def test_sound_run_is_correct(cell):
    res = run_cell(cell)
    assert correct(res), res["checks"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_training_fault_is_caught(fault):
    hooks = ({"wrap_step": unchanged_state} if fault == "unchanged_state"
             else {"loss_fn": half_batch_loss})
    res = run_cell("tonn-hjb20d.train", **hooks)
    assert not correct(res), res["checks"]


def test_altered_answer_is_caught():
    res = run_cell("tonn-hjb20d.serve-80", wrap_program=altered_answer)
    assert not correct(res), res["checks"]
