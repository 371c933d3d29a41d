"""``loop_idle_share`` (dispatch): the share of the window the resident loop's
thread sat in ``resident.idle``, with nothing queued and nothing in flight
(0 where it never starved; nothing where the program has no such span)."""


def read(ctx: dict) -> float | None:
    c, seconds = ctx["counters"], ctx["win"]["seconds"]
    if not seconds or "resident.issue_wave.count" not in c:
        return None
    return 100.0 * c.get("resident.idle.total_ms", 0.0) / (1000.0 * seconds)
