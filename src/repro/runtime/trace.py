"""Host spans and counters of the program, on the profiler's clock.

A ``Recorder`` times named regions of host code and sums them per name;
each region is also a ``jax.profiler.TraceAnnotation``, so it sits beside
the device's ops in any profiler trace an operator takes.  With no
profiler running, a span costs one annotation object, two clock reads and
two dict updates (a few microseconds).

    rec = Recorder()
    with rec.span("engine.step"):
        with rec.span("engine.pack"):
            ...
    rec.add("queue_wait_s", 0.002)
    rec.summary()  # {"spans": {name: {...}}, "counters": {name: value}}

Per span name the summary holds ``count``, ``seconds`` (wall time inside
the span), ``self_seconds`` (``seconds`` less the spans opened inside it)
and ``max_s`` (the longest single span).  Every span name contains a
``.`` (``<layer>.<part>``), which keeps the program's spans apart from the
benchmark's own one-word span names in a shared trace.

Not thread-safe: one recorder belongs to one thread's loop.
"""

from __future__ import annotations

from time import perf_counter

import jax

__all__ = ["Recorder"]


class _Span:
    """One open span; ``Recorder.span`` returns it for a ``with``."""

    __slots__ = ("rec", "name", "note", "t0")

    def __init__(self, rec: "Recorder", name: str):
        self.rec, self.name = rec, name
        self.note = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.note.__enter__()
        self.rec._children.append(0.0)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        self.note.__exit__(*exc)
        self.rec._close(self.name, dt)
        return False


class Recorder:
    """Per-name sums of host spans, and counters."""

    def __init__(self):
        self._spans: dict = {}      # name -> [count, seconds, self, max]
        self._counters: dict = {}
        self._children: list = []   # seconds of closed children, per open span

    def span(self, name: str) -> _Span:
        """A context manager that times the enclosed block as ``name``."""
        if "." not in name:
            raise ValueError(f"span name {name!r} has no '.': name it "
                             "<layer>.<part>")
        return _Span(self, name)

    def _close(self, name: str, dt: float) -> None:
        child = self._children.pop()
        if self._children:
            self._children[-1] += dt
        acc = self._spans.get(name)
        if acc is None:
            self._spans[name] = [1, dt, dt - child, dt]
        else:
            acc[0] += 1
            acc[1] += dt
            acc[2] += dt - child
            acc[3] = max(acc[3], dt)

    def add(self, name: str, value) -> None:
        """Add ``value`` to the counter ``name``."""
        self._counters[name] = self._counters.get(name, 0) + value

    def summary(self) -> dict:
        """Plain floats and ints: ``{"spans": {...}, "counters": {...}}``."""
        return {
            "spans": {name: {"count": int(c), "seconds": float(s),
                             "self_seconds": float(own), "max_s": float(m)}
                      for name, (c, s, own, m) in self._spans.items()},
            "counters": dict(self._counters)}
