"""``tail_offcpu_ms`` (results tail): mean ms that a pool thread spent off the
CPU inside ``query.results_work`` (the results built under the core lock, so
every ms here is a ms every other tail waits), ready or blocked but not
running: the span's mean wall time less its mean ``query.results_work.cpu_us``
(the thread's CPU clock read at both ends) / 1000. A program that does not
count the span's CPU: nothing to read."""

NAME = "query.results_work"


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    n = c.get(f"{NAME}.count", 0)
    if not n or f"{NAME}.cpu_us" not in c:
        return None
    return (c[f"{NAME}.total_ms"] - c[f"{NAME}.cpu_us"] / 1000.0) / n
