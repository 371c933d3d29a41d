"""``stall_ms`` (front door): milliseconds of the window, in whole seconds,
that saw under half the median second's answers."""

import statistics


def read(ctx: dict) -> float | None:
    win = ctx["win"]
    n = int(win["seconds"])
    per = [0] * n
    for r in win["rows"]:
        k = int(r["done"] - win["open"])
        if r["status"] == 200 and 0 <= k < n and r["done"] >= win["open"]:
            per[k] += 1
    if not per or statistics.median(per) == 0:
        return None
    return 1000.0 * sum(1 for c in per if c < statistics.median(per) / 2)
