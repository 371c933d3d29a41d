"""``stages_sum_ms`` (front door): what the stages of the program's request
ledger come to for one request, from the window's own totals: each stage's
total over the count of what carries it (a request; a batch, whose stages every
rider holds; a wave, whose stages every ticket's riders hold). Beside
``front_unseen_ms`` it is the client's mean latency where the timeline closes
(in a closed loop that is clients / ``traced_qps``); a batch or wave weighs
once however many rode it."""

#: carrier span -> the stages timed once for each of it (``query.lock_wait``
#: is met twice a batch, so its total over the batches is both waits)
CARRIED = {
    "serve.request": ("admission.queue_delay", "batcher.queue_wait",
                      "batcher.wake", "serve.render", "serve.unaccounted"),
    "batcher.pool_wait": ("batcher.pool_wait", "query.lock_wait",
                          "resident.queue_wait", "query.results_work"),
    "resident.issue_wave": ("resident.issue_wave", "resident.inflight_wait",
                            "resident.collect_wave"),
}


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    if not c.get("serve.request.count"):
        return None
    total = 0.0
    for carrier, stages in CARRIED.items():
        n = c.get(f"{carrier}.count", 0)
        if n:
            total += sum(c.get(f"{s}.total_ms", 0.0) for s in stages) / n
    return total
