"""``traced_qps`` (front door): good answers of this, the traced, run's window
a second. Beside ``qps`` of the untraced runs it is what tracing costs when on."""


def read(ctx: dict) -> float | None:
    win = ctx["win"]
    good = sum(1 for r in win["rows"] if r["status"] == 200
               and win["open"] <= r["done"] < win["close"])
    return good / win["seconds"] if win["seconds"] else None
