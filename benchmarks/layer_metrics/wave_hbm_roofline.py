"""``wave_hbm_roofline`` (kernels): the least time memory bandwidth allows for
the bytes the span's queries need (lib/measure.needed_bytes) over the wave
programs' device time. Bound by bytes: the wave does no matrix work."""

from lib import spec


def read(ctx: dict) -> float | None:
    s = spec.plugin("layer_metrics", "_wave").wave_seconds(ctx)
    nb = ctx.get("needed_bytes")
    if not s or not nb:
        return None
    return 100.0 * (nb / ctx["peaks"]["hbm_bytes_per_s"]) / s
