"""Whole-step share of the chips' peak, in %: the model FLOPs of one
ZO-signSGD step of the ONN configuration (bench/counts_onn.py: each layer
the cheaper of its meshes on its rows and densify + product, and the head,
over the N + 1 parameter sets) times the steps per second of the traced
window, over chips × peak FLOP/s."""

from _common import module


def read(ctx):
    trace, steps = ctx.get("trace"), ctx.get("steps_traced")
    if trace is None or not steps:
        return None
    job = ctx["mix"]
    flops = module("counts_onn").step_flops(ctx["config"], job,
                                            job["zo_samples"] + 1)
    rate = steps / trace.window_s
    return 100.0 * flops * rate / (ctx["chips"] * ctx["peak"]["flops_per_s"])
