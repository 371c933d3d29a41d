"""``issue_ms`` (dispatch): mean of the program's span ``resident.issue_wave``:
plan, route and the asynchronous dispatch of one wave on the loop's thread."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "resident.issue_wave")
