"""Host cost of one ``trace.timed_span`` enter and exit, a span of
``trace.CPU_SPANS`` against one off the list.

    python3 tools/span_bench.py [--root <checkout>] [--reps 31] [--n 20000]

Times ``with timed_span(name): pass`` for a name on the CPU list
(``resident.issue_wave``: two ``thread_time`` reads and one counter
increment more) and one off it (``resident.collect_wave``), with no
trace sampled, no ledger bound and no profiler session: the cost every
served request pays with tracing off. Each of ``--reps`` rounds times
``--n`` spans of each name, interleaved, on the host's clock. The median
of the rounds and their quartiles, in µs a span, of each name, of their
difference within a round and of one ``time.thread_time()`` read, go out
as one JSON line. ``--root`` imports the package from another checkout
(one without the CPU list times both names as plain spans). Host CPU
only; not run by any cell.
"""

import argparse
import json
import statistics
import sys
import time
from pathlib import Path


def _per_span_us(fn, n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--reps", type=int, default=31)
    ap.add_argument("--n", type=int, default=20000)
    a = ap.parse_args()
    sys.path.insert(0, a.root)
    from open_source_search_engine_tpu.utils import trace
    from open_source_search_engine_tpu.utils.stats import g_stats

    def span_of(name):
        def one():
            with trace.timed_span(name):
                pass
        return one

    def read_clock():
        time.thread_time()

    cases = {"listed": span_of("resident.issue_wave"),
             "unlisted": span_of("resident.collect_wave"),
             "thread_time": read_clock}
    rows: dict[str, list[float]] = {k: [] for k in cases}
    for fn in cases.values():          # warm
        _per_span_us(fn, a.n // 10)
    for _ in range(a.reps):
        for k, fn in cases.items():
            rows[k].append(_per_span_us(fn, a.n))
        g_stats.reset()                # keep the histograms small
    out = {"root": a.root,
           "cpu_list": list(getattr(trace, "CPU_SPANS", ())),
           "reps": a.reps, "n": a.n}
    # a round's two spans ran side by side: their difference is steadier
    # than either median on a shared host
    rows["listed_less_unlisted"] = [
        x - y for x, y in zip(rows["listed"], rows["unlisted"])]
    for k, v in rows.items():
        q = statistics.quantiles(v, n=4)
        out[k] = {"median_us": statistics.median(v), "q1_us": q[0],
                  "q3_us": q[2]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
