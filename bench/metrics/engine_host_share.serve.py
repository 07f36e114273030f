"""Share, in %, of the traced window the serving thread spends inside the
engine: the ``engine.submit`` and ``engine.step`` spans (``serving_stats()``
``spans``) over the window's seconds.  The whole loop, the drain included,
runs inside the window, so this is the utilization of the one host thread
that sets the knee."""


def read(ctx):
    spans = (ctx.get("counters") or {}).get("spans")
    trace = ctx.get("trace")
    if not spans or trace is None or "engine.step" not in spans:
        return None
    seconds = spans["engine.step"]["seconds"] + spans.get(
        "engine.submit", {}).get("seconds", 0.0)
    return 100.0 * seconds / trace.window_s
