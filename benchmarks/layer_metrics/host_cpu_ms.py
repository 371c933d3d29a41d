"""``host_cpu_ms`` (front door): the process's CPU time an answered query, in
ms: the sum of ``host.cpu_us.<role>`` over the window / 1000 / the window's
``query`` count. A program without the CPU account: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    cpu = [v for k, v in c.items() if k.startswith("host.cpu_us.")]
    queries = c.get("query", 0)
    if not cpu or not queries:
        return None
    return sum(cpu) / 1000.0 / queries
