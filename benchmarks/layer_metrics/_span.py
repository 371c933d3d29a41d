"""Mean milliseconds of one of the program's spans over the window."""


def mean_ms(ctx: dict, name: str) -> float | None:
    c = ctx["counters"]
    n = c.get(f"{name}.count", 0)
    return c.get(f"{name}.total_ms", 0.0) / n if n else None
