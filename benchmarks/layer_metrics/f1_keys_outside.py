"""``f1_keys_outside`` (dispatch): ``_two_phase`` dispatches in the window
whose program key lies outside the closed set the index enumerated at
start-up (``devindex.f1.key_outside_set``): each may be a program met for the
first time. 0 where F1 is closed."""


def read(ctx: dict) -> float | None:
    return float(ctx["counters"].get("devindex.f1.key_outside_set", 0.0))
