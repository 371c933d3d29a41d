"""``rider_wakes`` (batching): returns from a rider's wait in the batcher a
query, over the window (``batcher.rider_wake`` / ``query``). 1 is the least:
the one wake that finds its result; the batcher's ``notify_all`` wakes every
parked rider at every enqueue, result and pool job's end. A program without
the counter: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    queries = c.get("query", 0)
    if "batcher.rider_wake" not in c or not queries:
        return None
    return c["batcher.rider_wake"] / queries
