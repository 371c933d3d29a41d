"""``pool_wait_ms`` (batching): mean of the program's span ``batcher.pool_wait``,
a formed batch's wait for a thread of the batcher's pool."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "batcher.pool_wait")
