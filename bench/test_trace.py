"""Self-test of the trace reduction (bench/xtrace.py).

Run by path (not part of the repository's test suite):
    python -m pytest bench/test_trace.py

``testdata/serve_tonn.xplane.pb`` is a trace recorded on one TPU v5e: the
paper-width tonn solver served through ``PdeServingEngine`` for a few
engine steps, inside the benchmark's ``window``, ``submit`` and
``engine_step`` spans.  The reduction is checked against a brute-force
reading of the same events, and its nesting rule on small made-up
intervals.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH)]

import xtrace  # noqa: E402

TRACE = BENCH / "testdata" / "serve_tonn.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(str(TRACE))


def test_finds_device_window_and_spans(trace):
    assert trace.devices() == [0]
    assert 0 < trace.window_s < 1.0
    names = {n for n, _, _ in trace.spans}
    assert {"submit", "engine_step"} <= names
    ops = {n for n, *_ in trace.ops[0]}
    # ops carry their HLO instruction names, kernels under their own name
    assert any(xtrace.kernel_match("tt_contract")(n) for n in ops)
    assert not any(n.startswith("%") or " = " in n for n in ops)


def test_busy_is_the_union_of_op_intervals(trace):
    lo, hi = trace.window
    grid = np.linspace(lo, hi, 400_001)
    covered = np.zeros_like(grid, dtype=bool)
    for _, s, e, _ in trace.ops[0]:
        covered |= (grid >= s) & (grid < e)
    brute = covered.mean() * (hi - lo)
    assert trace.busy_s(0) == pytest.approx(brute, rel=1e-3, abs=1e-6)
    assert 0 < trace.busy_s(0) <= trace.window_s


def test_self_times_add_up_to_busy_time(trace):
    """Ops do not overlap except by nesting, so the self times of all ops
    add up to the busy time."""
    total = trace.op_seconds(lambda n: True)
    assert total == pytest.approx(trace.busy_s(0), rel=1e-6)
    assert sum(s for _, s in trace.top_ops(10)) <= total * (1 + 1e-9)


def test_idle_gaps_lie_in_the_window(trace):
    gaps = trace.idle_gaps(1000)
    idle = trace.window_s - trace.busy_s(0)
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    labels = {n for n, _ in gaps}
    assert labels <= {"data", "dispatch", "sync", "generator", "submit",
                      "engine_step", "none"}


def test_nested_ops_keep_their_self_time():
    events = [("while", 0.0, 10.0), ("body_a", 1.0, 3.0),
              ("body_b", 4.0, 8.0), ("inner", 5.0, 6.0), ("after", 11.0, 12.0)]
    got = {n: own for n, _, _, own in xtrace.self_times(events)}
    assert got == {"while": 4.0, "body_a": 2.0, "body_b": 3.0, "inner": 1.0,
                   "after": 1.0}


def test_op_names():
    assert xtrace.op_name(
        "%tt_contract.5 = f32[11,4300,1024]{2,1,0} custom-call(...)") == \
        "tt_contract.5"
    assert xtrace.op_name("all-reduce.1 = f32[12] all-reduce(...)") == \
        "all-reduce.1"
    assert xtrace.is_collective("all-reduce.1")
    assert not xtrace.is_collective("fusion.3")
    assert xtrace.kernel_match("tt_contract")("tt_contract.12")
    assert not xtrace.kernel_match("tt_contract")("tt_contract_quant.1")
