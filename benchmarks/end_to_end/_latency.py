"""Latency of every request of the window: from when it was sent (closed
loop) or was due (open loop) to its last byte. A request belongs to the window
when that moment lies in it; one that failed or was shed counts as slower than
any (the request's time limit, or the slowest answer if that is slower)."""

from lib import measure


def latencies_ms(win: dict) -> list[float]:
    key = "due" if win["loop"] == "open" else "sent"
    rows = [r for r in win["rows"] if win["open"] <= r[key] < win["close"]]
    ok = [1000.0 * (r["done"] - r[key]) for r in rows if r["status"] == 200]
    worst = max(ok + [1000.0 * win["timeout_s"]])
    return ok + [worst] * (len(rows) - len(ok))


def percentile_ms(win: dict, q: float) -> float | None:
    v = latencies_ms(win)
    return measure.percentile(v, q) if v else None
