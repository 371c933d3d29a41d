"""``f1_share`` (dispatch): of the window's queries, the share the program
first routed to the two-phase kernel (``devindex.route.f1`` over all three
route counters). A program without the counters: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    routed = sum(c.get(f"devindex.route.{r}", 0) for r in ("f1", "fd", "f2"))
    return 100.0 * c.get("devindex.route.f1", 0) / routed if routed else None
