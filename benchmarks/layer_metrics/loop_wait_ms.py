"""``loop_wait_ms`` (dispatch): mean of the program's span ``resident.queue_wait``,
a ticket's wait from ``submit`` to the resident loop taking it."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "resident.queue_wait")
