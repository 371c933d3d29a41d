"""``plan_ms`` (planning): mean of the program's span ``devindex.plan``."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "devindex.plan")
