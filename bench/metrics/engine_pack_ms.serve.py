"""Host ms per engine step in ``engine.pack``: admission, grouping and
filling the pool buffers, from the engine's own spans (``serving_stats()``
``spans``; ``engine.pack`` seconds over the ``engine.step`` count)."""


def read(ctx):
    spans = (ctx.get("counters") or {}).get("spans")
    if not spans or "engine.pack" not in spans or "engine.step" not in spans:
        return None
    return spans["engine.pack"]["seconds"] / spans["engine.step"]["count"] \
        * 1e3
