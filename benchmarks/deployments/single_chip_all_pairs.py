"""Deployment ``single_chip_all_pairs``: ``single_chip`` for questions whose
score needs every term pair in the min (five to eight words, the ``T`` 8
bucket).

The same server, corpus, set-up, spans and counters as ``single_chip``: this
file adds one question put to the program once the chip is reached, before
anything is compiled. Does its scorer leave term pairs out of the min (does
it define ``scorer.MAX_PAIR_SPAN``)? Such a program answers a question of six
words or more by another rule than the reference's, and its fused kernel
unrolls the pairs it keeps: compiling its ``T`` 8 programs ran a one-chip v5e
machine out of its 40 GiB of host memory about eleven minutes into the cell's
first run. On such a program the run ends at once, with no result.
"""

from __future__ import annotations

from lib import spec

_base = spec.plugin("deployments", "single_chip")


class Deployment(_base.Deployment):
    def reach_chip(self) -> dict:
        device = super().reach_chip()
        from open_source_search_engine_tpu.query import scorer
        if hasattr(scorer, "MAX_PAIR_SPAN"):
            raise RuntimeError(
                "this program's scorer leaves term pairs farther apart than "
                f"{scorer.MAX_PAIR_SPAN} out of the min, so it cannot serve "
                "questions of five to eight words here")
        return device
