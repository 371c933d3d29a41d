"""``f1_t8_device_ms`` (device wave): device time of the ``_two_phase``
programs in the traced span, an F1 answer at ``T`` 8, counted as
``f1_wave_device_ms`` counts: the span's such answers are its answers times
the window's ``t8_f1_share``. The trace names a module by its program and not
by its ``T``, so this reads only where every F1 answer of the window was at
``T`` 8 (``devindex.route.f1.t8`` equal to ``devindex.route.f1``). A program
without the counter: nothing to read."""

from lib import spec


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    if c.get("devindex.route.f1.t8") != c.get("devindex.route.f1"):
        return None
    tr = ctx.get("trace")
    share = spec.plugin("layer_metrics", "t8_f1_share").read(ctx)
    n = ctx.get("answers_in_span", 0)
    if not tr or not tr.get("modules") or not share or not n:
        return None
    s = sum(v for k, v in tr["modules"].items() if "_two_phase" in k)
    return 1000.0 * s / (n * share / 100.0) if s > 0 else None
