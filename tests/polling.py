"""Condition-polling helpers for tests that wait on another thread.

A fixed ``time.sleep(X)`` encodes a guess about scheduler timing: too
short flakes under load, too long taxes every run. Poll the actual
condition instead — running the suite on saturated boxes exposed
exactly these guesses.
"""

from __future__ import annotations

import time


def wait_until(cond, timeout: float = 5.0, interval: float = 0.005,
               desc: str = "condition"):
    """Poll ``cond()`` until truthy; return its value. Raises
    ``AssertionError`` (with ``desc``) on timeout so a hung wait reads
    as a test failure, not an error."""
    end = time.monotonic() + timeout
    while True:
        v = cond()
        if v:
            return v
        if time.monotonic() >= end:
            raise AssertionError(
                f"wait_until: {desc} not reached in {timeout}s")
        time.sleep(interval)
