"""Host ms per program run in ``engine.launch``: the pool buffer's copy to
the device and the compiled program's call, up to its return (dispatch is
asynchronous), from the engine's own spans (``serving_stats()`` ``spans``)."""


def read(ctx):
    spans = (ctx.get("counters") or {}).get("spans")
    if not spans or "engine.launch" not in spans:
        return None
    span = spans["engine.launch"]
    return span["seconds"] / span["count"] * 1e3
