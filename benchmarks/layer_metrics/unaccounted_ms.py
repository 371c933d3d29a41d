"""``unaccounted_ms`` (front door): mean of the program's span ``serve.unaccounted``:
what a request's stages leave of ``serve.request``."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "_span").mean_ms(ctx, "serve.unaccounted")
