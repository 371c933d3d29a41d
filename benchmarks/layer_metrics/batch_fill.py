"""``batch_fill`` (QueryBatcher): queries a device batch, over the window."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    batches = c.get("query.results_batch.count", 0)
    return c.get("query", 0) / batches if batches else None
