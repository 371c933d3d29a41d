"""``host_cpu_share`` (front door): the process's CPU time over the window, by
every thread role (``host.cpu_us.<role>``: loop, batcher, pool, front, other),
as a share of one core: 100 x their sum / the window's µs. 100 is one core,
about the most Python the one interpreter lock can run; ``other`` holds the
threads the runtime starts outside Python too. A program without the CPU
account: nothing to read."""


def read(ctx: dict) -> float | None:
    c, seconds = ctx["counters"], ctx["win"]["seconds"]
    cpu = [v for k, v in c.items() if k.startswith("host.cpu_us.")]
    if not cpu or not seconds:
        return None
    return 100.0 * sum(cpu) / (1e6 * seconds)
