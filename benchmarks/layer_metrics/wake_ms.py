"""``wake_ms`` (batching): mean of the stage ``batcher.wake``, from the pool
thread setting a rider's result to the rider's thread running again. Read
beside ``rider_wakes``, where the program counts its riders' wakes: a program
without that count reports neither."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    n = c.get("batcher.wake.count", 0)
    if not n or "batcher.rider_wake" not in c:
        return None
    return c["batcher.wake.total_ms"] / n
