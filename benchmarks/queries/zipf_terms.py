"""Query rule ``zipf_terms``: unique queries of 1..max_terms Zipf-drawn words.

The rule of ``bench._make_queries`` (listed in PERF.md for a later PR to
delete): each query's length is drawn evenly from 1..max_terms and its words
Zipf(zipf_a) over the vocabulary; a query seen before is drawn again, so deep
in a long list the short queries thin out as a short vocabulary's run out. The
whole list comes from ``seed``; a longer list of one seed starts with the
shorter one. Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


def make(seed: int, n: int, p: dict) -> list[str]:
    rng = np.random.default_rng([int(seed), 0x51])
    seen: set[str] = set()
    out: list[str] = []
    while len(out) < n:
        n_terms = int(rng.integers(1, p["max_terms"] + 1))
        terms = rng.zipf(p["zipf_a"], size=n_terms) % p["vocab"]
        q = " ".join(f"word{t}" for t in terms)
        if q not in seen:
            seen.add(q)
            out.append(q)
    return out
