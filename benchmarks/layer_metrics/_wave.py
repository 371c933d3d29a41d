"""Device seconds of the wave programs in the traced span."""


def wave_seconds(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("modules"):
        return None
    s = sum(v for k, v in tr["modules"].items() if ctx["is_wave"](k))
    return s if s > 0 else None
