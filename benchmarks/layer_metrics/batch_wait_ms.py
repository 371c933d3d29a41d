"""``batch_wait_ms`` (batching): mean of the program's span ``batcher.queue_wait``,
a rider's wait from its enqueue to the forming of its batch."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "batcher.queue_wait")
