"""chip_smoke.py on a CPU: every phase runs, then it fails.

The no-fallback rule, pinned: the script that proves the served path on
the chip must not pass anywhere else. At 300 documents the whole path —
corpus build, device base, HTTP serving of every route, the comparison
with the host flat path — runs here (fused kernels in interpret mode),
and the only checks that fail are the ones a CPU cannot meet."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(*args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    return proc, [json.loads(ln) for ln in proc.stdout.splitlines()]


@pytest.fixture(scope="module")
def smoke():
    return _run("--docs", "300")


def test_fails_at_once_as_the_driver_runs_it():
    """No arguments, no accelerator: non-zero before any corpus is
    built (the chip's 100,000 documents would take a CPU an hour)."""
    proc, lines = _run()
    assert proc.returncode != 0, proc.stdout
    assert [ln.get("phase") for ln in lines[:-1]] == ["device"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"


def test_fails_without_an_accelerator(smoke):
    proc, lines = smoke
    assert proc.returncode != 0, proc.stdout
    last = lines[-1]
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert not any(ln.get("ok") is True for ln in lines)


def test_only_the_device_checks_fail(smoke):
    """Everything a CPU can show holds: natives loaded, every route ran
    through HTTP, recall@10 = 1.0 against the host flat path, no
    fallback, no compile in the served pass."""
    proc, lines = smoke
    failed = lines[-1]["failed"]
    assert failed and all(f.startswith(("device:", "kernels:"))
                          for f in failed), (failed, proc.stderr[-2000:])
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert by_phase["native"]["native"] == "loaded"
    rc = by_phase["routes"]["route_counts"]
    assert rc["f1"] > 0 and rc["fd"] > 0 and rc["f2"] > 0
    fb = by_phase["fallbacks"]
    assert fb["serve.device_fallback"] == 0
    assert fb["build.devbuild_fallback"] == 0
    assert fb["recall_at_10"] == 1.0 and fb["answers"] >= 24
    assert fb["served_compiles"] == 0 and fb["served_retraces"] == 0
