"""``device_idle_share`` (device): the share of the traced span in which no
operation ran on the chip."""


def read(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
