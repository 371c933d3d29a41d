"""``p50_ms``: the median latency of the window's requests."""

from lib import spec


def read(win: dict) -> float | None:
    return spec.plugin("end_to_end", "_latency").percentile_ms(win, 50)
