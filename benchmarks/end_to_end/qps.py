"""``qps``: good answers that arrived while the window was open, a second."""


def read(win: dict) -> float | None:
    good = sum(1 for r in win["rows"] if r["status"] == 200
               and win["open"] <= r["done"] < win["close"])
    return good / win["seconds"]
