"""The program's host-span recorder (``repro.runtime.trace``): per-name
sums, self time under nesting, counters, ``max_s``, and spans that reach a
profiler trace on the host plane."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.runtime import Recorder
from repro.runtime import trace as trace_mod


@pytest.fixture
def clock(monkeypatch):
    """A clock that reads the next value of ``ticks`` on each call."""
    ticks = []
    monkeypatch.setattr(trace_mod, "perf_counter", lambda: ticks.pop(0))
    return ticks


def test_nesting_and_self_time(clock):
    rec = Recorder()
    # outer 0..10 holds a.child 1..3 and a.child 4..7 (a.leaf 5..6 inside)
    clock.extend([0.0, 1.0, 3.0, 4.0, 5.0, 6.0, 7.0, 10.0])
    with rec.span("a.outer"):
        with rec.span("a.child"):
            pass
        with rec.span("a.child"):
            with rec.span("a.leaf"):
                pass
    spans = rec.summary()["spans"]
    assert spans["a.outer"] == {"count": 1, "seconds": 10.0,
                                "self_seconds": 5.0, "max_s": 10.0}
    assert spans["a.child"] == {"count": 2, "seconds": 5.0,
                                "self_seconds": 4.0, "max_s": 3.0}
    assert spans["a.leaf"] == {"count": 1, "seconds": 1.0,
                               "self_seconds": 1.0, "max_s": 1.0}
    assert not clock


def test_counters_and_max(clock):
    rec = Recorder()
    clock.extend([0.0, 1.0, 1.0, 5.0, 5.0, 7.0])
    for _ in range(3):
        with rec.span("x.y"):
            pass
    rec.add("waits_s", 0.25)
    rec.add("waits_s", 0.5)
    rec.add("admitted", 1)
    rec.add("admitted", 1)
    summary = rec.summary()
    assert summary["spans"]["x.y"] == {"count": 3, "seconds": 7.0,
                                       "self_seconds": 7.0, "max_s": 4.0}
    assert summary["counters"] == {"waits_s": 0.75, "admitted": 2}
    assert isinstance(summary["counters"]["admitted"], int)
    json.dumps(summary)                  # plain floats and ints


def test_span_closes_on_exception(clock):
    """A raising block is still timed and leaves the nesting intact."""
    rec = Recorder()
    clock.extend([0.0, 1.0, 2.0, 4.0])
    with rec.span("a.outer"):
        with pytest.raises(ValueError):
            with rec.span("a.inner"):
                raise ValueError("client error")
    spans = rec.summary()["spans"]
    assert spans["a.inner"]["seconds"] == 1.0
    assert spans["a.outer"]["self_seconds"] == 3.0


def test_span_name_needs_a_dot():
    with pytest.raises(ValueError, match="no '.'"):
        Recorder().span("engine_step")


def test_span_names_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    rec = Recorder()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with rec.span("engine.step"):
            with rec.span("engine.fetch"):
                jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    host = [p for p in ProfileData.from_file(files[0]).planes
            if p.name == "/host:CPU"]
    names = {ev.name for p in host for line in p.lines for ev in line.events}
    assert {"engine.step", "engine.fetch"} <= names
    assert rec.summary()["spans"]["engine.step"]["count"] == 1
