"""The benchmark's entry.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip and serves; the load generator is a child that
never imports jax. The last line of standard output is the result; every
stage says when it began on standard error (``at_s``), with a heartbeat every
10 s. After the last line the process is gone at once. README.md says how a
cell, a configuration, a traffic mix or a metric is added as files only.
"""

import time

T0 = time.perf_counter()            # the run's clock starts before any import

import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

if __name__ == "__main__":
    from lib import runner
    runner.main(sys.argv[1:], t0=T0)    # never returns: it exits by force
