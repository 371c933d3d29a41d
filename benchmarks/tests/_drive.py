"""Drives the rest of a run past the harness's look for a chip, on the CPU at
a tiny size, with one fault planted in the program underneath. Prints the
result line the run made. ``python3 _drive.py <fault> <run.py's arguments>``."""

import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def plant(fault: str) -> None:
    from open_source_search_engine_tpu.query import engine
    from open_source_search_engine_tpu.serve import server
    if fault == "altered_answer":
        # an answer altered where it is produced: the best page's score
        build = engine.build_results

        def altered(*a, **kw):
            results, clustered = build(*a, **kw)
            if results:
                results[0].score *= 1.01
            return results, clustered
        engine.build_results = altered
    elif fault == "host_path":
        # every answer from the host flat path, the device path broken
        def broken(self, key, q, timeout=60.0):
            raise RuntimeError("planted: device path down")
        server.QueryBatcher.search = broken
    elif fault == "wrong_total":
        render = server.render_results

        def wrong(res, fmt, trace_id=None):
            res.total_matches += 1
            return render(res, fmt, trace_id=trace_id)
        server.render_results = wrong
    elif fault == "compile_in_window":
        # a program the set-up never met, compiled under every served request
        import jax
        import jax.numpy as jnp
        search, met = server.QueryBatcher.search, []

        def cold(self, key, q, timeout=60.0):
            met.append(1)
            jax.jit(lambda x: x + 1)(jnp.zeros(len(met))).block_until_ready()
            return search(self, key, q, timeout=timeout)
        server.QueryBatcher.search = cold
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault}")


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    from lib import runner, spec
    extra = [a for a in argv if a.startswith("--bench=")]
    if extra:       # a BENCHMARK.json of the test's own (discovery)
        argv = [a for a in argv if not a.startswith("--bench=")]
        path = extra[0].split("=", 1)[1]
        spec.benchmark = lambda: spec.load_json(Path(path))
    plant(fault)
    try:
        runner.main(argv, t0=T0, hard_exit=False, allow_cpu=True)
    except SystemExit:
        pass
    line = getattr(runner.main.last_run, "final_line", None)
    print(json.dumps(line))
    return 0 if line else 1


if __name__ == "__main__":
    sys.exit(main())
