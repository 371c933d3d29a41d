"""``wave_device_ms`` (device wave): device time of the wave programs an
answer of the traced span."""

from lib import spec


def read(ctx: dict) -> float | None:
    s = spec.plugin("layer_metrics", "_wave").wave_seconds(ctx)
    n = ctx.get("answers_in_span", 0)
    return 1000.0 * s / n if s and n else None
