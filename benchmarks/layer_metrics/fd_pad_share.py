"""``fd_pad_share`` (kernels): the share of FD wave lanes that held no query,
over the window (``devindex.fd.pad_lanes`` over ``devindex.fd.lanes``, both
counted where ``_run_batch_fd`` builds a wave: a ``B`` 4 wave with one query
adds 4 and 3). The fused FD kernel skips those lanes. A program without the
counters, or a window with no FD wave: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    lanes = c.get("devindex.fd.lanes", 0)
    if not lanes:
        return None
    return 100.0 * c.get("devindex.fd.pad_lanes", 0) / lanes
