"""One benchmark run that also keeps its window's CPU account by role.

    python3 tools/window_cpu.py --out <file.json> <benchmarks/run.py arguments>

Runs the benchmark's runner in this process, as ``benchmarks/run.py``
does, with the same arguments, result line and exit. The window's
counter deltas that the per-layer metrics do not print one by one are
written to ``--out`` as the runner takes them: ``host.cpu_us.<role>``
(each role's CPU µs), the spans of ``trace.CPU_SPANS`` (``.count``,
``.total_ms``, ``.cpu_us``), ``batcher.rider_wake``, ``batcher.wake``
and ``query``. The window's length is the ``--seconds`` given. Not run
by any benchmark cell.
"""

import json
import sys
import time

T0 = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
KEEP = ("host.cpu_us.", "resident.issue_wave.", "devindex.plan.",
        "query.results_work.", "batcher.rider_wake", "batcher.wake.")


def main() -> None:
    argv = sys.argv[1:]
    i = argv.index("--out")
    out = Path(argv[i + 1])
    del argv[i:i + 2]
    sys.path.insert(0, str(ROOT / "benchmarks"))
    sys.path.insert(0, str(ROOT))
    from lib import runner

    taken = []
    delta = runner.delta

    def keep(a: dict, b: dict) -> dict:
        d = delta(a, b)
        # the runner's first delta is the window's (open -> close)
        if not taken:
            taken.append({k: v for k, v in sorted(d.items())
                          if k.startswith(KEEP) or k == "query"})
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps({"argv": argv, "window": taken[0]}))
        return d

    runner.delta = keep
    runner.main(argv, t0=T0)


if __name__ == "__main__":
    main()
