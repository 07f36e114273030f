"""Share, in %, of the traced window in which no operation runs on the device,
averaged over the cell's chips (the ONN training cell)."""


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or "steps_traced" not in ctx:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s() / trace.window_s)
