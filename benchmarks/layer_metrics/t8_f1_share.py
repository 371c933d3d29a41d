"""``t8_f1_share`` (dispatch): of the window's queries, the share the program
first routed to the two-phase kernel at ``T`` 8, five to eight words
(``devindex.route.f1.t8`` over all three route counters). A program without
the counter: nothing to read."""


def t8_share(ctx: dict, route: str) -> float | None:
    c = ctx["counters"]
    name = f"devindex.route.{route}.t8"
    routed = sum(c.get(f"devindex.route.{r}", 0) for r in ("f1", "fd", "f2"))
    if name not in c or not routed:
        return None
    return 100.0 * c[name] / routed


def read(ctx: dict) -> float | None:
    return t8_share(ctx, "f1")
