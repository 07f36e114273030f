"""Whole-step share of the chips' peak, in %: the model FLOPs of one
ZO-signSGD step (bench/counts.py: factorized TT contractions, head, mesh
densification of the N + 1 parameter sets) times the steps per second of
the traced window, over chips × peak FLOP/s."""

from _common import module


def read(ctx):
    trace, steps = ctx.get("trace"), ctx.get("steps_traced")
    if trace is None or not steps:
        return None
    counts = module("counts")
    job = ctx["mix"]
    flops = counts.step_flops(ctx["config"], job, job["zo_samples"] + 1)
    rate = steps / trace.window_s
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peak"]["flops_per_s"])
