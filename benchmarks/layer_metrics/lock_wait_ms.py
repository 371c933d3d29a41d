"""``lock_wait_ms`` (results tail): mean of the program's span ``query.lock_wait``,
the wait for the server's core lock at either acquisition of the batch path."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "query.lock_wait")
