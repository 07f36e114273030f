"""The benchmark's readers of the serving engine's spans and counters
(``bench/metrics/*.serve.py``), loaded by path: each gives its value from
a ``serving_stats()`` dict and reads nothing where the engine has no spans
(a program without the recorder)."""

import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

METRICS = Path(__file__).resolve().parent.parent / "bench" / "metrics"


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(count, seconds):
    return {"count": count, "seconds": seconds, "self_seconds": seconds,
            "max_s": seconds}


def _ctx(with_spans=True):
    stats = {"steps": 200, "program_runs": 250, "points_served": 98_000,
             "cache_hits": 1_000, "cache_misses": 99_000,
             "requests_done": 1_200}
    if with_spans:
        stats.update({
            "spans": {"engine.submit": _span(1_200, 0.05),
                      "cache.lookup": _span(1_200, 0.04),
                      "engine.step": _span(200, 0.6),
                      "engine.pack": _span(200, 0.1),
                      "engine.launch": _span(250, 0.05),
                      "engine.fetch": _span(250, 0.2),
                      "cache.insert": _span(250, 0.11)},
            "queue_wait_s": 1.8, "requests_admitted": 1_200})
    return {"counters": stats, "trace": SimpleNamespace(window_s=3.25),
            "mix": {"slots": 8, "slot_points": 256}}


EXPECTED = {
    "engine_pack_ms.serve": 0.1 / 200 * 1e3,
    "engine_launch_ms.serve": 0.05 / 250 * 1e3,
    "engine_fetch_ms.serve": 0.2 / 250 * 1e3,
    "cache_host_us.serve": (0.04 + 0.11) / 100_000 * 1e6,
    "queue_wait_ms.serve": 1.8 / 1_200 * 1e3,
    "engine_host_share.serve": 100.0 * (0.05 + 0.6) / 3.25,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(name):
    assert _reader(name)(_ctx()) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_without_spans(name):
    assert _reader(name)(_ctx(with_spans=False)) is None
