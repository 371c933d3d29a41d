"""``loop_cpu_share`` (dispatch): the resident loop's thread's CPU time over
the window as a share of it: 100 x ``host.cpu_us.loop`` / the window's µs.
Near 100 the loop's thread is the serial stage; low while ``loop_idle_share``
is near 0, the loop waits (for the device or the interpreter lock). A program
without the CPU account: nothing to read."""


def read(ctx: dict) -> float | None:
    c, seconds = ctx["counters"], ctx["win"]["seconds"]
    if "host.cpu_us.loop" not in c or not seconds:
        return None
    return 100.0 * c["host.cpu_us.loop"] / (1e6 * seconds)
