"""``wave_host_ms`` (device wave, host side): mean of the program's span ``devindex.device``."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "devindex.device")
