"""``window_compiles`` (dispatch): programs compiled or loaded from the cache
while the window was open (jitwatch); the window is only steady at 0."""


def read(ctx: dict) -> float | None:
    return float(ctx["counters"].get("jit.compiles", 0))
