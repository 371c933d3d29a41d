"""The benchmark's own tests (run by hand: ``python3 -m pytest benchmarks/tests -q``;
they are not part of the repo's tier-1 run). The slow ones drive a whole run
on the CPU at a tiny size in a process of their own."""

import pytest

from _helpers import drive  # noqa: F401 -- also puts benchmarks/ on the path


@pytest.fixture(scope="session")
def sound_run():
    return drive("--workload", "gbshard-80k.mix-c32", "--seed", "77",
                 "--seconds", "2", "--trace", "0", "--rehearse-docs", "400")
