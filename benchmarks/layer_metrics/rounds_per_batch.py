"""``rounds_per_batch`` (resident loop, collect_batch): device fetch rounds a
wave issued; above 1 a query escalated."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    issued = c.get("resident.issue", 0)
    return c.get("devindex.device.count", 0) / issued if issued else None
