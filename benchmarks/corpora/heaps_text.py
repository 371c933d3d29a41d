"""Corpus generator ``heaps_text``: English-like pages over an open vocabulary.

The shapes of luceneutil's ``wikimedium10m`` line file (documents of about
1 KB: a title and a body), made from the seed because no session can read the
file itself: a page's words are drawn from a Zipf-Mandelbrot law
``p(r) ~ (r + q) ** -s`` over ``vocab`` ranks, so the vocabulary a corpus
really holds grows with its size as Heaps' law says (most ranks of the tail
never occur in a small corpus, tens of thousands occur in one page only), and
document frequencies span every class a tasks file picks from. Word ``r`` is
the token ``word<r>``; ids are ``int32``.

Pages are drawn in chunks of ``CHUNK`` with a generator each, so any slice of
the corpus can be made alone (by a corpus child, by the query rule, by the
reference) and is the same pages. A page's site is ``page % sites``, which is
what the plain reference (``reference/gb_minscore.py``) states. It imports
nothing of the program.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK = 1000


@functools.lru_cache(maxsize=4)
def _cdf(vocab: int, s: float, q: float) -> np.ndarray:
    w = (np.arange(1, vocab + 1, dtype=np.float64) + q) ** -s
    c = np.cumsum(w)
    return c / c[-1]


def chunk_word_ids(seed: int, chunk: int, p: dict) -> tuple[np.ndarray, np.ndarray]:
    """(words per page, word ids of all pages end to end) of one chunk."""
    rng = np.random.default_rng([int(seed), 0xC1, int(chunk)])
    lens = rng.integers(p["min_words"], p["max_words"] + 1, size=CHUNK)
    u = rng.random(int(lens.sum()))
    ids = np.searchsorted(_cdf(p["vocab"], p["zm_s"], p["zm_q"]), u)
    return lens.astype(np.int32), ids.astype(np.int32)   # u < 1 = cdf[-1]


def word_ids(seed: int, lo: int, hi: int, p: dict) -> tuple[np.ndarray, np.ndarray]:
    """Pages ``lo..hi-1``: (words per page [hi-lo], word ids end to end)."""
    lens_out, ids_out = [], []
    for chunk in range(lo // CHUNK, (hi + CHUNK - 1) // CHUNK):
        lens, ids = chunk_word_ids(seed, chunk, p)
        first = chunk * CHUNK
        a, b = max(lo, first) - first, min(hi, first + CHUNK) - first
        off = np.concatenate([[0], np.cumsum(lens)])
        lens_out.append(lens[a:b])
        ids_out.append(ids[off[a]:off[b]])
    return np.concatenate(lens_out), np.concatenate(ids_out)


def url_of(d: int, p: dict) -> str:
    return f"http://site{d % p['sites']}.bench.test/doc{d}"


def pages(seed: int, lo: int, hi: int, p: dict):
    """Yield (url, html) of pages ``lo..hi-1``."""
    lens, ids = word_ids(seed, lo, hi, p)
    sw, tw = p["sentence_words"], p["title_words"]
    at = 0
    for k, n in enumerate(lens):
        words = [f"word{i}" for i in ids[at:at + n]]
        at += n
        title = " ".join(words[:tw])
        sents = [" ".join(words[s:s + sw]) + "." for s in range(0, n, sw)]
        yield (url_of(lo + k, p),
               f"<html><head><title>{title}</title></head><body><p>"
               + " ".join(sents) + "</p></body></html>")


def doc_of_url(url: str) -> int | None:
    """The page number a url of this corpus names (None: not of this corpus)."""
    tail = url.rsplit("/doc", 1)
    return int(tail[1]) if len(tail) == 2 and tail[1].isdigit() else None


def postings_per_word(lens: np.ndarray, ids: np.ndarray, p: dict) -> np.ndarray:
    """How many postings each word's list holds (body and title occurrences)."""
    start = np.concatenate([[0], np.cumsum(lens.astype(np.int64))])[:-1]
    title = ids[(start[:, None] + np.arange(p["title_words"])).ravel()]
    return (np.bincount(ids, minlength=p["vocab"])
            + np.bincount(title, minlength=p["vocab"]))


def doc_freq(lens: np.ndarray, ids: np.ndarray, p: dict) -> np.ndarray:
    """In how many pages each word occurs (what a tasks file's classes are
    made from): [vocab] int64."""
    doc = np.repeat(np.arange(len(lens), dtype=np.int64), lens.astype(np.int64))
    pairs = np.unique(doc * p["vocab"] + ids.astype(np.int64))
    return np.bincount(pairs % p["vocab"], minlength=p["vocab"])
