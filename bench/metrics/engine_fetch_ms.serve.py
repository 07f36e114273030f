"""Host ms per program run in ``engine.fetch``: the wait for the device and
the copy of the result back to the host, from the engine's own spans
(``serving_stats()`` ``spans``)."""


def read(ctx):
    spans = (ctx.get("counters") or {}).get("spans")
    if not spans or "engine.fetch" not in spans:
        return None
    span = spans["engine.fetch"]
    return span["seconds"] / span["count"] * 1e3
