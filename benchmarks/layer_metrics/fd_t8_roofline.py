"""``fd_t8_roofline`` (kernels): the least time HBM bandwidth allows for the
bytes the span's FD answers at ``T`` 8 need from the fused kernel, over the
kernel's device time there (``fd_t8_device_ms``' seconds and answers). An
answer reads its ``T`` x 4 quarter rows of the resident cube, ``P/4`` x
``D_cap`` x 4 B each, and where its wave carries a posting tail the
``[T, P, D_cap]`` uint32 tail cube besides (``devindex.fd.t8_tail`` over
``devindex.route.fd.t8``: the window's share of such answers). The kernel is
bound by its vector work, so this reads far below 100%."""

from lib import spec

T, P, D_CAP = 8, 16, 131072
QUARTER_ROWS_BYTES = T * 4 * (P // 4) * D_CAP * 4     # 67,108,864
TAIL_BYTES = T * P * D_CAP * 4                         # 67,108,864


def read(ctx: dict) -> float | None:
    got = spec.plugin("layer_metrics", "fd_t8_device_ms") \
        .t8_seconds_and_answers(ctx)
    c = ctx["counters"]
    fd = c.get("devindex.route.fd.t8", 0)
    if not got or not fd or "devindex.fd.t8_tail" not in c:
        return None
    s, answers = got
    tail_share = c["devindex.fd.t8_tail"] / fd
    need = answers * (QUARTER_ROWS_BYTES + tail_share * TAIL_BYTES)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / s
