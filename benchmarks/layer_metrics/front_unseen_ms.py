"""``front_unseen_ms`` (front door): the client's mean latency over the window's
good answers less the program's mean ``serve.request`` (request line read to
last byte written): connect, accept, the handler thread's start and the
client's own read, which no span inside the program can cover."""

from lib import spec


def read(ctx: dict) -> float | None:
    win = ctx["win"]
    key = "due" if win["loop"] == "open" else "sent"
    ms = [1000.0 * (r["done"] - r[key]) for r in win["rows"]
          if r["status"] == 200 and win["open"] <= r["done"] < win["close"]]
    inside = spec.plugin("layer_metrics", "_span").mean_ms(ctx, "serve.request")
    if not ms or inside is None:
        return None
    return sum(ms) / len(ms) - inside
