"""Plain reference ``gb_minscore``: the ranking the configuration states, in
numpy and float64, over the pages the corpus generator makes from the seed.

It imports nothing of the program and takes nothing the program made: the
pages come from ``corpora/<generator>.py``, and positions, ranks and weights
are worked out here from the page's own layout
(``<title>`` of the first words, one ``<p>`` of sentences).

What it states (Gigablast's min-algorithm, Posdb.cpp, as the program's
docstrings quote it):

* a word's occurrences in a page, in position order, the first ``P`` kept
  (``P/2`` and the first ``P/4`` occurrences of the bigram with the next
  query word, where that bigram occurs anywhere in the corpus);
* a position's weight = hashgroup weight x density weight x spam weight;
* a word's own score = sum over hashgroups of the best 100*w*w, x tf weight^2;
* a pair's score = best over position pairs of 100*w_i*w_j/(dist+1), x the two
  tf weights, with the distance rules of ``_pair_best``;
* a page's score = the least of all of these, x the language boost;
* a match = a page that holds every word; the page of results = the ten best
  with at most ``max_per_site`` a site.
"""

from __future__ import annotations

import numpy as np

P = 16                      # positions kept for a word in a page
BASE = 100.0
FIXED_DISTANCE = 400.0
NONBODY_CAP = 50.0
QDIST = 2.0
LANG_BOOST = 20.0           # query language "any": every page gets the boost
W_TITLE, W_BODY = 8.0, 1.0
MAX_DENSITY, MAX_SPAM = 31, 15
TITLE_START, BODY_START = 4, 16     # word position of the first title / body word
SENT_GAP = 2
URL_WORDS = 5               # http, site<k>, bench, test, doc<d>


def density_weight(rank):
    return np.minimum(0.35 * 1.03445 ** np.asarray(rank, np.float64), 1.0)


class Reference:
    def __init__(self, lens: np.ndarray, ids: np.ndarray, corpus: dict,
                 max_per_site: int = 2, page: int = 10, weight_round=None):
        self.p = corpus
        self.lens = lens.astype(np.int64)
        self.ids = ids
        self.n_docs = len(lens)
        self.max_per_site = max_per_site
        self.page = page
        # the control's hook (tools/control.py): a lower precision for every
        # position weight; the reference itself has none
        self.weight_round = weight_round
        sw = self.sw = corpus["sentence_words"]
        self.tw = corpus["title_words"]
        start = np.concatenate([[0], np.cumsum(self.lens)])
        self.doc_of = np.repeat(np.arange(self.n_docs, dtype=np.int32),
                                self.lens)
        # every place's index in its page, its word position, and the density
        # weight of its sentence (the words in it; the last one may be short)
        self.local = (np.arange(len(ids), dtype=np.int64)
                      - np.repeat(start[:-1], self.lens)).astype(np.int32)
        self.pos = (BODY_START + self.local
                    + SENT_GAP * (self.local // sw)).astype(np.int32)
        # the last sentence of a page may be short: its words are denser
        last_at = (self.lens - 1) // sw
        last_len = (self.lens - sw * last_at).astype(np.int32)
        in_last = (self.local // sw) == np.repeat(last_at, self.lens)
        sent_len = np.where(in_last, np.repeat(last_len, self.lens), sw)
        table = density_weight(np.arange(MAX_DENSITY + 1))
        self.den_w = table[np.clip(MAX_DENSITY - (sent_len - 1), 1,
                                   MAX_DENSITY)]
        self.den_w_title = float(density_weight(
            max(MAX_DENSITY - (self.tw - 1), 1)))
        # every page's total token count, for the spam rule
        self.n_tokens = self.lens + self.tw + URL_WORDS
        # every word's places in the corpus, in order (made once)
        self._by_word = np.argsort(ids, kind="stable")
        self._bounds = np.searchsorted(ids[self._by_word],
                                       np.arange(corpus["vocab"] + 1))

    def _places(self, word: int) -> np.ndarray:
        return self._by_word[self._bounds[word]:self._bounds[word + 1]]

    # ------------------------------------------------------------ postings
    def _occurrences(self, word: int, nxt: int | None, quota: int):
        """The first ``quota`` postings a page of ``word`` (or of the bigram
        ``word nxt``), in (page, position) order: page, position, in_body,
        weight. Title places (the page's first words, said again in the
        title) come before the body's."""
        sw, tw = self.sw, self.tw
        at = self._places(word)                 # flat places of the word
        local, doc = self.local[at], self.doc_of[at]
        # spam: the share of the page's tokens that are this word
        cnt = np.bincount(doc, minlength=self.n_docs) \
            + np.bincount(doc[local < tw], minlength=self.n_docs)
        frac = cnt / self.n_tokens
        spam = np.where(frac > 0.125,
                        np.maximum(2, (MAX_SPAM * (1.0 - frac) * 0.8
                                       ).astype(np.int64)), MAX_SPAM)
        spam_w = (spam + 1.0) / (MAX_SPAM + 1.0)
        if nxt is not None:
            ok = at + 1 < len(self.ids)
            follows = np.zeros(len(at), bool)
            follows[ok] = self.ids[at[ok] + 1] == nxt
            in_body = follows & ((local + 1) % sw != 0) \
                & (local + 1 < self.lens[doc])
            in_title = follows & (local < tw - 1)
        else:
            in_body = np.ones(len(at), bool)
            in_title = local < tw
        t, b = at[in_title], at[in_body]
        d_t, d_b = self.doc_of[t], self.doc_of[b]
        # keep a page's first ``quota``: its title places, then its body's
        n_title = np.bincount(d_t, minlength=self.n_docs)
        keep = self._rank_in_doc(d_b) + n_title[d_b] < quota
        b, d_b = b[keep], d_b[keep]
        w_b = W_BODY * self.den_w[b] * spam_w[d_b]
        w_t = W_TITLE * self.den_w_title * spam_w[d_t]
        doc = np.concatenate([d_t, d_b]).astype(np.int64)
        pos = np.concatenate([TITLE_START + self.local[t], self.pos[b]])
        body = np.concatenate([np.zeros(len(t), bool), np.ones(len(b), bool)])
        w = np.concatenate([w_t, w_b])
        if self.weight_round is not None:
            w = self.weight_round(w)
        # title places lie before the body's in a page: a stable sort by page
        # alone leaves each page's places in position order
        order = np.argsort(doc, kind="stable")
        return doc[order], pos[order], body[order], w[order]

    @staticmethod
    def _rank_in_doc(doc: np.ndarray) -> np.ndarray:
        n = len(doc)
        if n == 0:
            return np.empty(0, np.int64)
        new = np.ones(n, bool)
        new[1:] = doc[1:] != doc[:-1]
        idx = np.arange(n)
        return idx - np.maximum.accumulate(np.where(new, idx, 0))

    def _group(self, word: int, nxt: int | None):
        """One query word's planes over the pages that hold it:
        pages [n], and [n, P] arrays of position, in_body, weight, valid."""
        subs = [(0, P)]
        bigram = None
        if nxt is not None:
            bigram = self._occurrences(word, nxt, P // 4)
            if len(bigram[0]):  # the bigram occurs somewhere: it takes a quarter
                subs = [(0, P // 2), (P // 2, P // 4)]
        occ = [self._occurrences(word, None, subs[0][1])] + \
            ([bigram] if len(subs) > 1 else [])
        pages = np.unique(occ[0][0])
        n = len(pages)
        planes = {"pos": np.zeros((n, P), np.float64),
                  "body": np.zeros((n, P), bool),
                  "w": np.zeros((n, P), np.float64),
                  "valid": np.zeros((n, P), bool)}
        for (d, ps, bo, ww), (base, quota) in zip(occ, subs):
            r = self._rank_in_doc(d)
            keep = r < quota
            row = np.searchsorted(pages, d[keep])
            col = base + r[keep]
            planes["pos"][row, col] = ps[keep]
            planes["body"][row, col] = bo[keep]
            planes["w"][row, col] = ww[keep]
            planes["valid"][row, col] = True
        return pages, planes, n

    # ------------------------------------------------------------- scoring
    @staticmethod
    def _pair_best(a: dict, b: dict) -> np.ndarray:
        """Best placement of words a (earlier in the query) and b."""
        delta = b["pos"][:, None, :] - a["pos"][:, :, None]       # [n,P,P]
        d_plain = np.maximum(np.abs(delta), 2.0)
        body_a, body_b = a["body"][:, :, None], b["body"][:, None, :]
        mixed = body_a != body_b
        both_nb = ~body_a & ~body_b
        d_base = np.where(both_nb & (d_plain > NONBODY_CAP),
                          FIXED_DISTANCE, d_plain)
        d_adj = np.where(d_base >= QDIST, d_base - QDIST, d_base) \
            + (delta < 0)
        dist = np.where(mixed, FIXED_DISTANCE, d_adj)
        ok = a["valid"][:, :, None] & b["valid"][:, None, :]
        s = BASE * a["w"][:, :, None] * b["w"][:, None, :] / (dist + 1.0)
        return np.max(np.where(ok, s, 0.0), axis=(1, 2))

    @staticmethod
    def _single(g: dict) -> np.ndarray:
        s = BASE * g["w"] * g["w"] * g["valid"]
        title = np.max(np.where(~g["body"], s, 0.0), axis=1)
        body = np.max(np.where(g["body"], s, 0.0), axis=1)
        return title + body

    def scores(self, words: list[int]) -> tuple[np.ndarray, np.ndarray]:
        """(pages that match, their scores), for the query's word ids."""
        groups = []
        for k, wd in enumerate(words):
            nxt = words[k + 1] if k + 1 < len(words) else None
            groups.append(self._group(wd, nxt))
        match = groups[0][0]
        for pages, _, _ in groups[1:]:
            match = np.intersect1d(match, pages, assume_unique=True)
        if not len(match):
            return match, np.empty(0)
        tfw = [0.5 + min(df / max(self.n_docs, 1), 0.5)
               for _, _, df in groups]
        score = np.full(len(match), np.inf)
        CH = 2048
        for lo in range(0, len(match), CH):
            m = match[lo:lo + CH]
            g = []
            for pages, planes, _ in groups:
                rows = np.searchsorted(pages, m)
                g.append({k: v[rows] for k, v in planes.items()})
            best = np.full(len(m), np.inf)
            for i in range(len(g)):
                best = np.minimum(best, self._single(g[i]) * tfw[i] * tfw[i])
                for j in range(i + 1, len(g)):
                    best = np.minimum(
                        best, self._pair_best(g[i], g[j]) * tfw[i] * tfw[j])
            score[lo:lo + CH] = best
        return match, score * LANG_BOOST

    def answer(self, query: str) -> dict:
        """The reference's answer: matches, the page's score ladder, and
        every matching page's score by url number."""
        words = [int(t[4:]) for t in query.split()]
        match, score = self.scores(words)
        order = np.argsort(-score, kind="stable")
        per_site: dict[int, int] = {}
        ladder, page_docs = [], []
        sites = self.p["sites"]
        for k in order:
            if len(ladder) >= self.page or score[k] <= 0.0:
                break
            s = int(match[k]) % sites
            if per_site.get(s, 0) >= self.max_per_site:
                continue
            per_site[s] = per_site.get(s, 0) + 1
            ladder.append(float(score[k]))
            page_docs.append(int(match[k]))
        return {"total": int(len(match)), "ladder": ladder,
                "page_docs": page_docs,
                "score_of": dict(zip(match.tolist(), score.tolist()))}
