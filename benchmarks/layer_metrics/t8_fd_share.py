"""``t8_fd_share`` (dispatch): of the window's queries, the share the program
first routed to the direct-cube kernel at ``T`` 8, five to eight words
(``devindex.route.fd.t8`` over all three route counters). A program without
the counter: nothing to read."""

from lib import spec


def read(ctx: dict) -> float | None:
    return spec.plugin("layer_metrics", "t8_f1_share").t8_share(ctx, "fd")
