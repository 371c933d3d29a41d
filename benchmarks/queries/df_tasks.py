"""Query rule ``df_tasks``: conjunctive tasks picked by document-frequency class.

How luceneutil's ``wikimedium.10M.nostopwords.tasks`` was made: terms are
sorted by the corpus's own document frequency into High, Med and Low, the most
frequent words of all (the stop words) are left out, and a task category draws
its terms from its classes (``HighTerm``, ``AndHighMed`` ...). Here the corpus
is made from the seed, so the classes are counted from the generator's own
pages (the traffic file names the generator, its parameters and the page
count, which a by-hand test holds equal to the configuration's).

The list is built in blocks: every ``sum(block)`` consecutive queries hold
each category in its exact share, in an order drawn from the seed, so two
seeds send the same mix. A one-word category walks a seeded permutation of its
class (no term twice); a several-word category draws each word evenly within
its class, ``MedLow`` being a fair coin between Med and Low first, and draws
again where the query was seen before. A category that cannot give an unseen
query raises: set-up ends loudly, the window never meets a repeat. A longer
list of one seed starts with the shorter one. Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

#: the words of a category, by class, in the order they are written
CATEGORIES = {
    "LowTerm": ("Low",), "MedTerm": ("Med",), "HighTerm": ("High",),
    "AndHighHigh": ("High", "High"), "AndHighMed": ("High", "Med"),
    "AndHighLow": ("High", "Low"),
    "And3": ("High", "MedLow", "MedLow"),
    "And4": ("High", "MedLow", "MedLow", "MedLow"),
}
REDRAWS = 64        # tries for an unseen several-word query


def _generator(name: str):
    path = Path(__file__).resolve().parents[1] / "corpora" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_corpora_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def classes(df: np.ndarray, docs: int, p: dict) -> dict[str, np.ndarray]:
    """Word ids of each class, by the corpus's own document frequencies."""
    share = df / float(docs)
    c = p["classes"]
    stop = np.argsort(-df, kind="stable")[:int(c["stop_words"])]
    high = share >= c["high_min"]
    high[stop] = False
    return {"High": np.nonzero(high)[0],
            "Med": np.nonzero((share >= c["med"][0]) & (share <= c["med"][1]))[0],
            "Low": np.nonzero((share >= c["low"][0]) & (share <= c["low"][1]))[0]}


def corpus_classes(seed: int, p: dict) -> dict[str, np.ndarray]:
    corpus = p["corpus"]
    gen = _generator(corpus["generator"])
    lens, ids = gen.word_ids(seed, 0, int(corpus["docs"]), corpus["params"])
    return classes(gen.doc_freq(lens, ids, corpus["params"]),
                   int(corpus["docs"]), p)


def block_of(p: dict) -> list[str]:
    """One block's categories, each as often as its share says."""
    return [name for name, n in p["block"] for _ in range(int(n))]


def make(seed: int, n: int, p: dict) -> list[str]:
    cls = corpus_classes(seed, p)
    rng = np.random.default_rng([int(seed), 0x52])
    # a one-word category walks its own permutation of its class
    walk = {name: iter(rng.permutation(cls[kinds[0]]))
            for name, kinds in CATEGORIES.items() if len(kinds) == 1}

    def word(kind: str) -> int:
        if kind == "MedLow":
            kind = ("Med", "Low")[int(rng.integers(2))]
        return int(cls[kind][int(rng.integers(len(cls[kind])))])

    block = block_of(p)
    empty = sorted(k for k, v in cls.items() if not len(v))
    if empty:
        raise RuntimeError(f"query rule df_tasks: no unseen query can be "
                           f"made, class {empty} holds no word")
    seen: set[str] = set()
    out: list[str] = []
    while len(out) < n:
        for name in (block[i] for i in rng.permutation(len(block))):
            kinds = CATEGORIES[name]
            q = None
            if len(kinds) == 1:
                w = next(walk[name], None)
                q = None if w is None else f"word{int(w)}"
            else:
                for _ in range(REDRAWS):
                    ws = [word(k) for k in kinds]
                    if len(kinds) > 2:      # the High word is not always first
                        ws = [ws[i] for i in rng.permutation(len(ws))]
                    cand = " ".join(f"word{w}" for w in ws)
                    if len(set(ws)) == len(ws) and cand not in seen:
                        q = cand
                        break
            if q is None or q in seen:
                raise RuntimeError(
                    f"query rule df_tasks: category {name} has no unseen "
                    f"query left after {len(out)} queries (classes hold "
                    f"{ {k: len(v) for k, v in cls.items()} } words)")
            seen.add(q)
            out.append(q)
    return out[:n]


def categories_of(queries: list[str], cls: dict[str, np.ndarray]
                  ) -> list[str]:
    """Each query's category, told from its words' classes (for the tests)."""
    of = {int(w): k for k, ws in cls.items() for w in ws}
    names = {tuple(sorted(k.replace("MedLow", "*") for k in kinds)): name
             for name, kinds in CATEGORIES.items()}
    out = []
    for q in queries:
        ks = [of[int(t[4:])] for t in q.split()]
        key = tuple(sorted(k if k == "High" or len(ks) <= 2 else "*"
                           for k in ks))
        out.append(names[key])
    return out
