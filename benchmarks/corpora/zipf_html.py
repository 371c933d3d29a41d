"""Corpus generator ``zipf_html``: Zipf-vocabulary HTML pages, made from the seed.

A copy of the rule of ``bench._gen_docs`` (the original is listed in PERF.md,
Open questions, for a later PR to delete), with two changes the benchmark
needs: the seed is an argument, and the pages are drawn in chunks of
``CHUNK`` pages with a generator each, so that any slice of the corpus can be
made alone (by a corpus child, or by the reference) and is the same pages.

Nothing here has a public source: it is a stand-in (configs/*.json says for
what). It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

CHUNK = 1000


def chunk_word_ids(seed: int, chunk: int, p: dict) -> tuple[np.ndarray, np.ndarray]:
    """(words per page, word ids of all pages end to end) of one chunk."""
    rng = np.random.default_rng([int(seed), 0xC0, int(chunk)])
    lens = rng.integers(p["min_words"], p["max_words"] + 1, size=CHUNK)
    ids = rng.zipf(p["zipf_a"], size=int(lens.sum())) % p["vocab"]
    return lens.astype(np.int32), ids.astype(np.int16)


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
    names = np.array([f"word{i}" for i in range(p["vocab"])])
    sw, tw = p["sentence_words"], p["title_words"]
    at = 0
    for k, n in enumerate(lens):
        words = names[ids[at:at + n]]
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
