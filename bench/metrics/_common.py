"""Helpers the per-layer metric readers share."""

from __future__ import annotations

from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent


def module(name: str):
    import harness
    return harness.load_module(BENCH / f"{name}.py")


def per_step_ms(ctx: dict, match) -> float | None:
    """Device ms per traced step in the ops that ``match`` selects, averaged
    over the cell's devices; None where the trace holds no such op."""
    trace, steps = ctx.get("trace"), ctx.get("steps_traced")
    if trace is None or not steps:
        return None
    seconds = trace.op_seconds(match)
    return seconds / steps * 1e3 if seconds > 0 else None
