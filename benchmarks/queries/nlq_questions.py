"""Query rule ``nlq_questions``: questions of five to eight words, stop words kept.

How MS MARCO's queries look (Bajaj et al., arXiv:1611.09268): real questions
typed into a search box, about six words each, the stop words left in, and each
with a passage that answers it. The source's query file cannot be read here, so
a question is made from one page of the corpus the seed makes: its words are
that page's, in that page's order (the page is the passage that answers it),
1-3 of them stop words (the corpus's most frequent words) and the rest content
words. Every question therefore matches at least its page.

The list is built in blocks: every ``sum(block)`` consecutive questions hold
each class and each length in its exact share, in orders drawn from the seed.
A question's class is the df class of its rarest content word (the classes of
``df_tasks``, counted from the corpus's own pages): ``QHigh`` questions hold
High words only, ``QMed`` High and Med with at least one Med, ``QLow`` any of
the three with at least one Low. No word is said twice in a question and no
question twice in the list; a page that cannot give the question drawn is
redrawn, and a rule that cannot give an unseen one raises: set-up ends loudly,
the window never meets a repeat. A longer list of one seed starts with the
shorter one. Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

#: a class's code, and the codes its other content words may have
CLASSES = {"QLow": 0, "QMed": 1, "QHigh": 2}
_STOP = 3
REDRAWS = 256       # pages tried for an unseen question


def _load(kind: str, name: str):
    path = Path(__file__).resolve().parents[1] / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def corpus(seed: int, p: dict):
    """(words per page, word ids end to end, each word's code: Low 0, Med 1,
    High 2, stop 3, none -1) of the corpus the traffic file names."""
    c = p["corpus"]
    gen = _load("corpora", c["generator"])
    docs = int(c["docs"])
    lens, ids = gen.word_ids(seed, 0, docs, c["params"])
    df = gen.doc_freq(lens, ids, c["params"])
    cls = _load("queries", "df_tasks").classes(df, docs, p)
    code = np.full(len(df), -1, np.int8)
    for name, k in (("Low", 0), ("Med", 1), ("High", 2)):
        code[cls[name]] = k
    code[np.argsort(-df, kind="stable")[:int(p["classes"]["stop_words"])]] \
        = _STOP
    return lens, ids, code


def block_of(p: dict, key: str) -> list:
    """One block's entries of ``p[key]``, each as often as its share says."""
    return [name for name, n in p[key] for _ in range(int(n))]


def make(seed: int, n: int, p: dict) -> list[str]:
    lens, ids, code = corpus(seed, p)
    off = np.concatenate([[0], np.cumsum(lens.astype(np.int64))])
    rng = np.random.default_rng([int(seed), 0x4E4C51])
    classes, lengths = block_of(p, "block"), block_of(p, "lengths")
    if len(classes) != len(lengths):
        raise ValueError("query rule nlq_questions: a block of "
                         f"{len(classes)} classes and {len(lengths)} lengths")
    lo, hi = (int(x) for x in p["stop_words_per_question"])

    def question(c: int, n_words: int, n_stop: int) -> str | None:
        for _ in range(REDRAWS):
            d = int(rng.integers(len(lens)))
            words = ids[off[d]:off[d + 1]]
            # each distinct word at its first place in the page
            _, first = np.unique(words, return_index=True)
            first = np.sort(first)
            k = code[words[first]]
            stop = first[k == _STOP]
            own = first[k == c]
            rest = first[(k > c) & (k < _STOP)]
            if (len(stop) < n_stop or not len(own)
                    or len(own) + len(rest) < n_words - n_stop):
                continue
            one = rng.choice(own, 1)
            pool = np.setdiff1d(np.concatenate([own, rest]), one)
            places = np.sort(np.concatenate([
                one, rng.choice(pool, n_words - n_stop - 1, replace=False),
                rng.choice(stop, n_stop, replace=False)]))
            q = " ".join(f"word{int(w)}" for w in words[places])
            if q not in seen:
                return q
        return None

    seen: set[str] = set()
    out: list[str] = []
    while len(out) < n:
        for i, j in zip(rng.permutation(len(classes)),
                        rng.permutation(len(lengths))):
            name, n_words = classes[i], int(lengths[j])
            q = question(CLASSES[name], n_words,
                         int(rng.integers(lo, hi + 1)))
            if q is None:
                raise RuntimeError(
                    f"query rule nlq_questions: no unseen {name} question "
                    f"of {n_words} words after {len(out)} questions")
            seen.add(q)
            out.append(q)
    return out[:n]


def shape_of(query: str, code: np.ndarray) -> tuple[str, int, int]:
    """A question's (class, words, stop words), told from its words' codes
    (for the tests)."""
    k = [int(code[int(t[4:])]) for t in query.split()]
    content = [x for x in k if x != _STOP]
    name = {v: n for n, v in CLASSES.items()}[min(content)]
    return name, len(k), len(k) - len(content)
