"""``issue_overlap`` (dispatch): of the waves the resident loop issued, the
share it began with another wave still in flight (``DEPTH`` 2 at work). A
program without the loop's own spans counts no overlap: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    issued = c.get("resident.issue", 0)
    if not issued or "resident.issue_wave.count" not in c:
        return None
    return 100.0 * c.get("resident.issue_overlapped", 0) / issued
