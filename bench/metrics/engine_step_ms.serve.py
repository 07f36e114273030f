"""Host ms per ``PdeServingEngine.step()`` call that had work, from the
benchmark's span around each call (packing, program run, scatter, cache)."""


def read(ctx):
    steps = ctx.get("engine_steps")
    if not steps:
        return None
    return ctx["engine_step_s"] / steps * 1e3
