"""Host µs per looked-up point in the ``StencilCache``: the engine's
``cache.lookup`` (keys and lookup at submit) and ``cache.insert`` (the
step's results) spans, over the cache's hits plus misses
(``serving_stats()``)."""


def read(ctx):
    stats = ctx.get("counters") or {}
    spans = stats.get("spans")
    if not spans or "cache.lookup" not in spans:
        return None
    points = stats["cache_hits"] + stats["cache_misses"]
    if not points:
        return None
    seconds = spans["cache.lookup"]["seconds"] + spans.get(
        "cache.insert", {}).get("seconds", 0.0)
    return seconds / points * 1e6
