"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The benchmark records a few seconds of steady window with
``jax.profiler`` and wraps its own host work in ``TraceAnnotation`` spans
(``window`` around the traced loop; ``data``, ``dispatch``, ``sync``,
``generator``, ``submit``, ``engine_step`` and ``gc``, a garbage
collection of Python's, inside it).  This module reads
the file with ``jax.profiler.ProfileData`` alone and computes:

  * busy time per device: the union of the intervals in which an
    operation of the device's ``XLA Ops`` line runs, clipped to the window;
  * device time by operation name (HLO instruction names, so a Pallas
    kernel appears under its kernel name, e.g. ``tt_contract.3``);
  * idle gaps on each device inside the window, each labelled by the
    innermost benchmark span that covers its midpoint on the host.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_NAMES = ("data", "dispatch", "sync", "generator", "submit",
              "engine_step", "gc")
HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


@dataclasses.dataclass
class Trace:
    """Device op intervals and host spans of one trace, in seconds on the
    profiler's common clock.  ``ops`` holds each op's self time: an op
    that encloses others (a while loop around its body) keeps only the
    part of its interval its children do not cover."""

    ops: dict            # device id -> [(name, start_s, end_s, self_s)]
    spans: list          # [(name, start_s, end_s)] of the benchmark's spans
    window: tuple        # (start_s, end_s) of the "window" span

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def devices(self) -> list:
        return sorted(self.ops)

    def _clipped(self, dev: int) -> list:
        """(name, start, end, self seconds) of the ops inside the window,
        self time scaled by the share of the op inside it."""
        lo, hi = self.window
        out = []
        for n, s, e, own in self.ops[dev]:
            if e > lo and s < hi:
                cs, ce = max(s, lo), min(e, hi)
                share = (ce - cs) / (e - s) if e > s else 1.0
                out.append((n, cs, ce, own * share))
        return out

    def busy_intervals(self, dev: int) -> list:
        merged = []
        for _, s, e, _ in sorted(self._clipped(dev), key=lambda t: t[1]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self, dev: int) -> float:
        return sum(e - s for s, e in self.busy_intervals(dev))

    def mean_busy_s(self) -> float:
        devs = self.devices()
        return sum(self.busy_s(d) for d in devs) / len(devs)

    def op_seconds(self, match, dev: int | None = None) -> float:
        """Device seconds of the ops whose name satisfies ``match``, inside
        the window; averaged over devices unless ``dev`` is given."""
        devs = [dev] if dev is not None else self.devices()
        total = sum(own for d in devs for n, _, _, own in self._clipped(d)
                    if match(n))
        return total / len(devs)

    def top_ops(self, k: int = 10) -> list:
        """[(name, seconds)] of the k ops that took most device time,
        averaged over devices."""
        acc: dict = {}
        for d in self.devices():
            for n, _, _, own in self._clipped(d):
                acc[n] = acc.get(n, 0.0) + own
        n_dev = len(self.devices())
        return sorted(((n, t / n_dev) for n, t in acc.items()),
                      key=lambda t: -t[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """[(label, seconds)] of the k longest idle gaps inside the window on
        any device, labelled by the innermost benchmark span over the gap's
        midpoint ("none" where the host was in no benchmark span)."""
        gaps = []
        lo, hi = self.window
        for d in self.devices():
            t = lo
            for s, e in self.busy_intervals(d) + [[hi, hi]]:
                if s > t:
                    gaps.append((self._label((t + s) / 2), s - t))
                t = max(t, e)
        return sorted(gaps, key=lambda g: -g[1])[:k]

    def _label(self, t: float) -> str:
        best = None
        for n, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "none"



def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event: TPU traces name an
    op by its whole HLO text ("%tt_contract.5 = f32[...] custom-call(...)")."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def self_times(events: list) -> list:
    """[(name, start, end, self seconds)] for [(name, start, end)]: each
    op's duration less the part covered by the ops nested inside it."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, window = {}, [], None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = self_times(
                        [(op_name(ev.name), ev.start_ns * 1e-9,
                          ev.end_ns * 1e-9) for ev in line.events])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        window = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    elif ev.name in SPAN_NAMES:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9))
    if not ops:
        raise ValueError(f"{path}: no '{OPS_LINE}' line on any TPU plane")
    if window is None:
        raise ValueError(f"{path}: no 'window' span on {HOST_PLANE}")
    return Trace(ops=ops, spans=spans, window=window)


def kernel_match(prefix: str):
    """Matches HLO names of a kernel: ``prefix`` or ``prefix.<n>``."""
    pat = re.compile(rf"^{re.escape(prefix)}(\.\d+)?$")
    return lambda name: bool(pat.match(name))


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))
