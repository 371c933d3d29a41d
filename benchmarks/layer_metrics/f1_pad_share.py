"""``f1_pad_share`` (device wave): the share of F1 wave lanes that held no
query, over the window (``devindex.f1.pad_lanes`` over ``devindex.f1.lanes``,
both counted where ``_run_batch`` dispatches a served ``_two_phase`` wave: a
``B`` 4 wave with one query adds 4 and 3). ``_two_phase`` runs none of its
per-lane work for those lanes. A program without the counters, or a window
with no F1 wave: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    lanes = c.get("devindex.f1.lanes", 0)
    if not lanes:
        return None
    return 100.0 * c.get("devindex.f1.pad_lanes", 0) / lanes
