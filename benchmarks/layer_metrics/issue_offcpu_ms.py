"""``issue_offcpu_ms`` (dispatch): mean ms that the loop's thread spent off the
CPU inside ``resident.issue_wave`` (plan, route, asynchronous dispatch), ready
or blocked but not running: the span's mean wall time less its mean
``resident.issue_wave.cpu_us`` (the thread's CPU clock read at both ends) /
1000. A program that does not count the span's CPU: nothing to read."""

NAME = "resident.issue_wave"


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    n = c.get(f"{NAME}.count", 0)
    if not n or f"{NAME}.cpu_us" not in c:
        return None
    return (c[f"{NAME}.total_ms"] - c[f"{NAME}.cpu_us"] / 1000.0) / n
