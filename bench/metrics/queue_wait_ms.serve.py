"""Mean ms a request waits between ``submit`` and the first slot a chunk of
it takes, from the engine's counters (``serving_stats()`` ``queue_wait_s``
over ``requests_admitted``; requests served wholly from the cache never
wait and are not counted)."""


def read(ctx):
    stats = ctx.get("counters") or {}
    if "spans" not in stats or not stats.get("requests_admitted"):
        return None
    return stats["queue_wait_s"] / stats["requests_admitted"] * 1e3
