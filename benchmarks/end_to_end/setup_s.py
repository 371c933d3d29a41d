"""``setup_s``: process start to the window's opening."""


def read(win: dict) -> float | None:
    return win["setup_s"]
