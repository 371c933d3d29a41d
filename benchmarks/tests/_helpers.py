"""Helpers of the benchmark's tests."""

import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def drive(*argv: str, fault: str = "none", timeout: float = 600.0,
          env: dict | None = None) -> dict:
    """A whole run on the CPU in a new process, with ``fault`` planted in the
    program underneath; the result line the run would have printed."""
    p = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "_drive.py"), fault, *argv],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert lines, f"no line: rc {p.returncode}\n{p.stderr[-3000:]}"
    out = json.loads(lines[-1])
    out["_stderr"] = p.stderr
    return out
