"""``f1_wave_device_ms`` (device wave): device time of the ``_two_phase``
programs in the traced span an F1 answer. The span's F1 answers are its
answers times the window's ``f1_share`` (the route counters are read over the
window, not over the span). A program without the counters: nothing to read."""

from lib import spec


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    share = spec.plugin("layer_metrics", "f1_share").read(ctx)
    n = ctx.get("answers_in_span", 0)
    if not tr or not tr.get("modules") or not share or not n:
        return None
    s = sum(v for k, v in tr["modules"].items() if "_two_phase" in k)
    return 1000.0 * s / (n * share / 100.0) if s > 0 else None
