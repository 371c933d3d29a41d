"""The comparison that decides ``correct``.

It is made on answers the measured window itself was served: a sample of the
window's finished requests, drawn from the seed, with the longest query in it.
The plain reference answers the same queries over the same pages, and each
number below is held to a limit of its own (configs/<name>.json, ``check``;
PERF.md gives the readings each limit was set from):

* ``ladder_gap``: the served page's scores, rank by rank, against the
  reference's page (the ten best with at most two a site): the widest relative
  gap; a missing or an extra rank reads 1. Ties may pick other members of a
  run, so ranks are compared by score, not by page.
* ``doc_gap``: each served page's score against the reference's score of that
  very page: the widest relative gap; a page the reference does not match
  reads 1.
* ``total_gap``: served ``totalMatches`` against the reference's count.
* ``site_over``: served pages of one site beyond the configuration's cap.
* ``off_device``: answers of the window that came from the host path or the
  result cache (the program's own counters).
* ``unanswered``: sampled requests with no good answer at all.
"""

from __future__ import annotations

import json
import urllib.parse
from collections import Counter

import numpy as np


def draw_sample(rows: list[dict], queries: list[str], seed: int, n: int
                ) -> list[dict]:
    """``n`` of the window's finished requests, from the seed, the one with the
    most words (the longest answer to work out) always among them."""
    rows = sorted(rows, key=lambda r: r["q"])
    if len(rows) <= n:
        return rows
    rng = np.random.default_rng([int(seed), 0x5A])
    longest = max(rows, key=lambda r: (len(queries[r["q"]].split()),
                                       -r["q"]))
    rest = [r for r in rows if r is not longest]
    pick = rng.choice(len(rest), size=n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def compare(sample: list[dict], queries: list[str], answer_of, doc_of_url,
            max_per_site: int, off_device: float, limits: dict
            ) -> tuple[bool, dict, list[dict]]:
    """(correct, numbers {name: {value, limit}}, the worst rows)."""
    n = {k: 0.0 for k in ("ladder_gap", "doc_gap", "total_gap", "site_over",
                          "unanswered")}
    notes = []
    for r in sample:
        q = queries[r["q"]]
        try:
            ans = json.loads(r["body"]) if r["status"] == 200 else None
            served = ans["results"]
            total = int(ans["totalMatches"])
        except (ValueError, KeyError, TypeError):
            n["unanswered"] += 1
            notes.append({"q": q, "status": r["status"],
                          "error": r.get("error")})
            continue
        ref = answer_of(q)
        row = {"ladder_gap": 0.0, "doc_gap": 0.0}
        ladder = ref["ladder"]
        if len(served) != len(ladder):
            row["ladder_gap"] = 1.0
        for s, l in zip(served, ladder):
            row["ladder_gap"] = max(row["ladder_gap"],
                                    rel_gap(float(s["score"]), l))
        for s in served:
            d = doc_of_url(s["url"])
            rs = ref["score_of"].get(d) if d is not None else None
            row["doc_gap"] = max(row["doc_gap"], 1.0 if rs is None
                                 else rel_gap(float(s["score"]), rs))
        sites = Counter(urllib.parse.urlsplit(s["url"]).hostname
                        for s in served)
        row["site_over"] = float(max(
            [c - max_per_site for c in sites.values()] + [0]))
        row["total_gap"] = float(abs(total - ref["total"]))
        for k, v in row.items():
            n[k] = max(n[k], v)
        if any(row[k] > limits[k] for k in row):
            notes.append({"q": q, **row, "served_total": total,
                          "ref_total": ref["total"],
                          "served": [float(s["score"]) for s in served],
                          "ladder": ladder})
    n["off_device"] = float(off_device)
    numbers = {k: {"value": v, "limit": limits[k]} for k, v in n.items()}
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return ok, numbers, notes[:5]
