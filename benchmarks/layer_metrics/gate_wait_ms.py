"""``gate_wait_ms`` (front door): mean of the program's span ``admission.queue_delay``,
from ``admit`` entered to admitted."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "admission.queue_delay")
