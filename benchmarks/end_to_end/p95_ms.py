"""``p95_ms``: the 95th percentile of the window's latencies."""

from lib import spec


def read(win: dict) -> float | None:
    return spec.plugin("end_to_end", "_latency").percentile_ms(win, 95)
