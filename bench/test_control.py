"""The control of each cell's comparison comes out as not correct.

Run by path (not part of the repository's test suite), on the CPU:
    python -m pytest bench/test_control.py

The control is the plain reference computed one precision below the
configuration's (three-pass bfloat16 matmuls in place of float32 at
HIGHEST), put in the program's place.  ``bench/calibrate.py`` reads it
beside the program at the cell's widths; here on one seed, on the CPU,
against the cell's own limits: the program passes every number and the
control fails at least one.  The readings that set the limits come from
the chip, on more seeds (PERF.md).
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(BENCH / "drivers")]

import calibrate  # noqa: E402
import harness  # noqa: E402

SEED = 3_141_592_653


def verdicts(readings: dict, limits: dict) -> dict:
    return {k: readings[k] <= lim for k, lim in limits.items()
            if k in readings}


@pytest.mark.parametrize("cell", ["tonn-hjb20d.train", "tonn-hjb20d.serve-80"])
def test_control_fails_and_program_passes(cell):
    files = harness.cell_files(cell)
    cfg, mix, limits = files["config"], files["traffic"], files["checks"]
    if mix["generator"] == "zo_train":
        r = calibrate.train_readings(cfg, mix, SEED)
    else:
        r = calibrate.serve_readings(cfg, mix, SEED, 20000)
    program = verdicts(r["program"], limits)
    control = verdicts(r["control"], limits)
    assert program and all(program.values()), r["program"]
    assert not all(control.values()), r["control"]
