"""``window_programs`` (dispatch): how many distinct wave programs the window
dispatched: the slots of ``DeviceIndex.dispatches`` (a program key takes a
slot at its first dispatch, every dispatch counts its slot's counter) that
moved while the window was open. A program without the slots: nothing to
read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    slots = [v for k, v in c.items() if k.startswith("devindex.program_slot.")]
    return float(sum(1 for v in slots if v > 0)) if slots else None
