"""The program cover: which compiled programs a window can meet, and how
set-up makes that set as nearly the same for every seed as it can.

A *key* names one compiled wave program (the program's own dispatch record).
Keys of one *class* differ by the batch bucket, which only a burst reaches.
Set-up (0) plans the window's whole query list on the host and primes the
programs it reaches, in one order; (1) walks the list's head (what the window
sends at today's rates), one query a batch; (2) for each
class that at least ``burst`` walked queries ride, sends one burst of
``burst`` walked queries of that class's commonest key, so the next batch
bucket of every common class is compiled in the cell's first run and loaded in
every later one; a class fewer queries ride gets none: a rare key may cost a
run one cold program, never two; (3) in the cell's first run in a checkout
plans a long list on the host only and runs one query for each key the walk
had not met; (4) prints the cover and checks it against the checkout's file.
"""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path


def prime_order(dry: list[list[str]], n_head: int | None = None,
                known: set[str] | None = None, cost_class=None
                ) -> tuple[list[int], list[str]]:
    """One query (its index) for each program key the list reaches, in sorted
    key order: the order in which set-up first meets the programs is then the
    same for every seed. The list's head (the first ``n_head`` queries, which
    set-up walks) has every key primed. A key that only the tail reaches is
    primed where that costs a cache load or a short compile: the checkout's
    cover file holds it (``known``), or its ``cost_class`` is not ``slow``.
    A slow key that only the tail reaches and the checkout never compiled is
    left, and named in the second list: a run pays a cold minute for what its
    window is sure to send, not for what it would send at four times the rate.
    """
    n_head = len(dry) if n_head is None else n_head
    first_of: dict[str, int] = {}
    for i, keys in enumerate(dry):
        for k in keys[:1]:
            first_of.setdefault(k, i)
    left = [k for k, i in sorted(first_of.items())
            if i >= n_head and k not in (known or ())
            and cost_class is not None and cost_class(k) == "slow"]
    return [first_of[k] for k in sorted(first_of) if k not in left], left


def known_keys(path: Path) -> set[str]:
    """The keys the checkout's cover file holds (none before the first run)."""
    return set(json.loads(path.read_text())["keys"]) if path.is_file() \
        else set()


def plan_bursts(walk_keys: list[list[str]], key_class, burst: int
                ) -> list[dict]:
    """One burst per class that ``burst`` or more walked queries ride:
    {"class", "key", "queries": indexes of walked queries of that key}."""
    by_class: dict[str, Counter] = {}
    of_key: dict[str, list[int]] = {}
    for i, keys in enumerate(walk_keys):
        if not keys:
            continue
        k = keys[0]                      # the first rung decides the class
        c = key_class(k)
        if c is None:
            continue
        by_class.setdefault(c, Counter())[k] += 1
        of_key.setdefault(k, []).append(i)
    out = []
    for c in sorted(by_class):
        if sum(by_class[c].values()) < burst:
            continue
        key, n = by_class[c].most_common(1)[0]
        if n < burst:
            continue        # the class is common, no one key of it is
        # queries that rode this one program and no further rung come first:
        # the program starts a query it has seen escalate at the higher rung,
        # which splits the burst and leaves the rest under the next bucket
        # (6 of 12 runs' F1 bursts, PERF.md Findings PR 27)
        ids = sorted(of_key[key], key=lambda i: len(walk_keys[i]) > 1)
        out.append({"class": c, "key": key, "queries": ids[:burst]})
    return out


def plan_wide(dry: list[list[str]], have: set[str], cost_class,
              caps: dict[str, int]) -> list[dict]:
    """Keys the wider host-only plan reaches and set-up has not met, commonest
    first, capped per cost class: {"key", "n", "query": index, "skipped"}."""
    count: Counter = Counter()
    first: dict[str, int] = {}
    for i, keys in enumerate(dry):
        for k in keys:
            if k not in have:
                count[k] += 1
                first.setdefault(k, i)
    used: Counter = Counter()
    out = []
    for k, n in count.most_common():
        cc = cost_class(k)
        skipped = used[cc] >= caps.get(cc, 0)
        if not skipped:
            used[cc] += 1
        out.append({"key": k, "n": n, "query": first[k], "skipped": skipped})
    return out


def check_file(path: Path, cover: dict[str, dict]) -> tuple[bool, list[dict]]:
    """Compare this run's cover with the checkout's file and bring the file up
    to date. Returns (was there a file, keys the file did not hold with what
    each cost)."""
    had = path.is_file()
    known = known_keys(path)
    new = [{"key": k, "cost_s": round(v["first_s"], 2)}
           for k, v in sorted(cover.items()) if k not in known]
    if new or not had:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(
            {"keys": sorted(set(known) | set(cover))}, indent=1))
        tmp.replace(path)
    return had, new
