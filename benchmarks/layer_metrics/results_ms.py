"""``results_ms`` (results tail): mean of the program's span ``query.results_batch``."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "query.results_batch")
