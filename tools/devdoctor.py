"""devdoctor — what backend did this process get?

A measurement or a server that silently runs on the CPU backend of a
TPU host files host numbers next to device numbers. This probe makes
the resolved backend loud and machine-readable:

* ``probe()`` initializes the backend ONCE — an init failure raises,
  nothing retries and nothing is carried past it — and records
  ``jax.devices()[0].platform``, its ``device_kind``, the device
  count, topology and ``memory_stats()`` (null where the backend has
  none — CPU).
* The verdict distinguishes ``ok`` (accelerator up), ``no-accelerator``
  (CPU box, CPU run — benign) and ``fallback`` (a TPU was expected —
  the environment says so — but jax resolved CPU: exit 1).
* ``stamp()`` is the memoized record of one process's backend.

CLI: ``python -m tools.devdoctor`` prints the probe JSON and exits
0 (ok), 1 (fallback — a TPU host is misbehaving; an init failure
raises), 2 (no accelerator present — benign on CI boxes).

Callers: an operator at the command line (the first thing to run on a
chip machine that misbehaves), and ``tests/test_devwatch.py``, which
holds the record's keys and the CPU verdict. The benchmark's runner
and ``chip_smoke.py`` name the device on their own lines; neither
imports this module.
"""

from __future__ import annotations

import json
import os
import sys

EXIT_OK = 0
EXIT_FALLBACK = 1
EXIT_NO_ACCEL = 2

_stamp_cache: dict | None = None


def tpu_expected() -> bool:
    """Does the environment claim a TPU should be reachable? A CPU
    resolution under these signals is a silent fallback, not a benign
    CPU run."""
    plat = os.environ.get("JAX_PLATFORMS", "")
    if "tpu" in plat:
        return True
    if plat:  # explicitly forced elsewhere (cpu CI runs land here)
        return False
    return any(k.startswith(("TPU_", "LIBTPU")) for k in os.environ)


def probe() -> dict:
    """Initialize the backend and return the diagnosis record."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    platform = str(d0.platform)
    if platform != "cpu":
        status = "ok"
    elif tpu_expected():
        status = "fallback"
    else:
        status = "no-accelerator"
    ms = d0.memory_stats()
    return {
        "doctor": status,
        "platform": platform,
        "jax_version": jax.__version__,
        "device_kind": str(d0.device_kind),
        "device_count": len(devs),
        "topology": {
            "process_count": int(jax.process_count()),
            "devices": [str(d) for d in devs[:16]],
            "coords": [list(getattr(d, "coords", ()) or ())
                       for d in devs[:16]],
        },
        "memory_stats": ({k: int(v) for k, v in ms.items()}
                         if ms else None),
    }


def stamp() -> dict:
    """The memoized per-process backend record (``probe()``, once)."""
    global _stamp_cache
    if _stamp_cache is None:
        _stamp_cache = probe()
    return dict(_stamp_cache)


def diagnose(rec: dict) -> str:
    """One actionable paragraph per verdict."""
    s = rec["doctor"]
    if s == "ok":
        return (f"backend ok: {rec['platform']} × "
                f"{rec['device_count']} ({rec['device_kind']})")
    if s == "no-accelerator":
        return ("no accelerator present and none expected — CPU "
                "numbers are host-measured, device_measured stays "
                "false")
    return ("TPU expected (JAX_PLATFORMS/TPU_*/LIBTPU* say so) but jax "
            "resolved the CPU backend. Check that libtpu matches the "
            "jax version, that no other process holds the TPU, and "
            "that JAX_PLATFORMS is not forcing cpu; numbers measured "
            "now would be mislabeled host points.")


def main() -> int:
    rec = probe()
    print(json.dumps(rec, indent=2))
    print(f"# {diagnose(rec)}", file=sys.stderr)
    if rec["doctor"] == "fallback":
        return EXIT_FALLBACK
    if rec["doctor"] == "no-accelerator":
        return EXIT_NO_ACCEL
    return EXIT_OK


if __name__ == "__main__":
    sys.exit(main())
