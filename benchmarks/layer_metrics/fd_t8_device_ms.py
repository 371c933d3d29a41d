"""``fd_t8_device_ms`` (kernels): device time of the fused FD kernel at ``T`` 8
(ops named ``fd_scores_fused_t8`` / ``fd_scores_fused_notail_t8``) in the
traced span, an FD answer at ``T`` 8. The span's such answers are its answers
times the window's ``t8_fd_share``, as ``f1_wave_device_ms`` counts. A program
without the names or the counter: nothing to read."""

import re

from lib import spec

OP = re.compile(r"^fd_scores_fused(_notail)?_t8(\.\d+)?$")


def t8_seconds_and_answers(ctx: dict) -> tuple[float, float] | None:
    tr = ctx.get("trace")
    share = spec.plugin("layer_metrics", "t8_fd_share").read(ctx)
    n = ctx.get("answers_in_span", 0)
    if not tr or not share or not n:
        return None
    s = sum(v for k, v in tr.get("ops", []) if OP.match(k))
    return (s, n * share / 100.0) if s > 0 else None


def read(ctx: dict) -> float | None:
    got = t8_seconds_and_answers(ctx)
    return 1000.0 * got[0] / got[1] if got else None
