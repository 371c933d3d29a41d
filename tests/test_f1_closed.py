"""The closed F1 program space, and the text configuration that forces it.

ISSUE 30: ``gbshard-text-80k`` queries an open vocabulary by
document-frequency class, so the riders of one ``_two_phase`` wave differ
in dense rows, sparse rows and run lengths. The index enumerates the
programs such waves can ride (``DeviceIndex.f1_programs``) without seeing
a query, a server dispatches each once before it listens
(``warm_f1``), and the program says which route each query took.

The corpus generator, the query rule and the plain reference are the
benchmark's own files (``benchmarks/``), loaded by path: they import
nothing of the program.
"""

import importlib.util
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query import devindex, engine
from open_source_search_engine_tpu.query.compiler import compile_query
from open_source_search_engine_tpu.utils.stats import g_stats

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
DOCS, SEED = 400, 4242
#: the cell's classes are shares of 80,000 pages; at 400 pages the same
#: rule needs wider ones (Low: 1-3 pages, Med: 4-14, High: 16 and more)
CLASSES = {"stop_words": 8, "high_min": 0.04, "med": [0.01, 0.035],
           "low": [0.0025, 0.0075]}


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"t_bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def text(tmp_path_factory):
    """400 pages of the text configuration, indexed; its rule's queries."""
    cfg = json.loads((BENCH / "configs" / "gbshard-text-80k.json"
                      ).read_text())
    mix = json.loads((BENCH / "traffic" / "mix4-c32.json").read_text())
    gen, rule = _load("corpora", "heaps_text"), _load("queries", "df_tasks")
    p = cfg["corpus"]["params"]
    qp = json.loads(json.dumps(mix["queries"]["params"]))
    qp["corpus"]["docs"], qp["classes"] = DOCS, CLASSES
    coll = Collection("main", tmp_path_factory.mktemp("text"))
    docproc.index_batch(coll, list(gen.pages(SEED, 0, DOCS, p)))
    coll.conf.pqr_enabled = False
    coll.dump_all()
    lens, ids = gen.word_ids(SEED, 0, DOCS, p)
    cls = rule.corpus_classes(SEED, qp)
    queries = rule.make(SEED, 200, qp)
    return {"cfg": cfg, "gen": gen, "rule": rule, "p": p, "qp": qp,
            "coll": coll, "lens": lens, "ids": ids, "cls": cls,
            "queries": queries,
            "cats": rule.categories_of(queries, cls),
            "ref": _load("reference", "gb_minscore").Reference(lens, ids, p),
            "compare": _load("lib", "compare")}


class _Unlowered:
    """``_two_phase`` for ``warm_f1``'s trace-then-compile step: nothing is
    traced or compiled."""

    def lower(self, *args, **statics):
        return self

    def compile(self):
        return None


@pytest.fixture
def no_dispatch(monkeypatch):
    """``_costed`` notes the program key and dispatches nothing: the shape
    logic runs whole, nothing compiles."""
    keys = []

    def note(self, name, bucket, modeled_bytes, fn, *args, **statics):
        keys.append((name, tuple(int(x) for x in bucket)))
        return None
    monkeypatch.setattr(devindex.DeviceIndex, "_costed", note)
    monkeypatch.setattr(devindex, "_two_phase", _Unlowered())
    return keys


def _count(name: str) -> float:
    return g_stats.snapshot()["counters"].get(name, 0)


def _of_one_page(text, n_words: int, count: int) -> list[str]:
    """Queries of one High word and Med or Low words that share a page."""
    of = {int(w): k for k, ws in text["cls"].items() for w in ws}
    off = np.r_[0, np.cumsum(text["lens"])]
    out = []
    for d in range(DOCS):
        ws = list(dict.fromkeys(
            int(w) for w in text["ids"][off[d]:off[d + 1]]))
        high = [w for w in ws if of.get(w) == "High"]
        rest = [w for w in ws if of.get(w) in ("Med", "Low")]
        if high and len(rest) >= n_words - 1:
            out.append(" ".join(f"word{w}"
                                for w in rest[:n_words - 1] + high[:1]))
        if len(out) == count:
            break
    return out


@pytest.mark.parametrize("category", [
    "LowTerm", "MedTerm", "HighTerm", "AndHighHigh", "AndHighMed",
    "AndHighLow", "And3", "And4"])
def test_system_matches_the_plain_reference(text, category):
    """Scores to 1e-4 relative, exact ``totalMatches``, two a site, through
    ``engine.search_device_batch`` — the comparison that decides ``correct``,
    on each task category."""
    qs = [q for q, c in zip(text["queries"], text["cats"])
          if c == category][:6]
    assert len(qs) == 6
    if category in ("And3", "And4"):
        # at 400 pages three rare words hardly ever share a page: half of
        # the six are made of one page's own words, by the same classes
        qs[3:] = _of_one_page(text, int(category[-1]), 3)
    res = engine.search_device_batch(text["coll"], qs, topk=10,
                                     with_snippets=False)
    rows = [{"q": i, "status": 200, "body": json.dumps({
        "totalMatches": r.total_matches,
        "results": [{"url": x.url, "score": x.score} for x in r.results]})}
        for i, r in enumerate(res)]
    ok, numbers, notes = text["compare"].compare(
        rows, qs, text["ref"].answer, text["gen"].doc_of_url, 2, 0.0,
        text["cfg"]["check"]["limits"])
    assert ok, (numbers, notes)
    assert any(r.total_matches for r in res)


def test_any_merge_of_f1_plans_rides_an_enumerated_program(text, no_dispatch):
    """The invariant: whatever F1 plans ``_issue_waves`` puts into one
    ``_run_batch`` call, the program key is one the index enumerated from
    itself. 200 seeded merges of two to four plans of the rule's queries
    (and of the largest waves a first rung takes)."""
    di = engine.get_device_index(text["coll"])
    enumerated = set(di.f1_programs())
    # the stated bounds: nine at T_FLOOR, three at F1_WIDE_T
    assert len([k for k in enumerated if len(k) == 6]) <= 9
    assert len([k for k in enumerated if len(k) == 7]) <= 3
    assert len(enumerated) == len(di.f1_programs())
    plans = [di.plan(compile_query(q, 0)) for q in text["queries"]]
    plans = [p for p in plans if p.matchable]
    rng = np.random.default_rng(30)
    before = _count("devindex.f1.key_outside_set")
    sizes = [int(rng.integers(2, 5)) for _ in range(200)] + [5, 17, 40]
    for n in sizes:
        pick = [plans[i] for i in rng.choice(len(plans), n, replace=False)]
        del no_dispatch[:]
        di._issue_waves(pick, list(range(n)), [], 64, 128, 2048, 4)
        assert no_dispatch and len(no_dispatch) <= n
        for name, bucket in no_dispatch:
            assert name == "devindex._two_phase"
            assert bucket in enumerated, (bucket, [
                (len(p.d_slot), len(p.s_start)) for p in pick])
    assert _count("devindex.f1.key_outside_set") == before
    # ... and what lies outside the set is counted: a nine-word query (T 16)
    wide = di.plan(compile_query(
        " ".join(f"word{i}" for i in range(1, 10)), 0))
    di._run_batch([wide], 256, 256)
    assert _count("devindex.f1.key_outside_set") == before + 1


def test_warm_f1_dispatches_the_enumerated_set_once(text, no_dispatch):
    di = devindex.DeviceIndex(text["coll"])
    n0 = _count("devindex.f1.programs_enumerated")
    assert di.warm_f1() == len(di.f1_programs())
    assert [b for _, b in no_dispatch] == di.f1_programs()
    assert _count("devindex.f1.programs_enumerated") - n0 == len(no_dispatch)
    assert g_stats.snapshot()["latencies"]["devindex.warm_f1"]["count"] >= 1
    assert di.warm_f1() == 0            # a warmed index: nothing to do


def test_a_server_warms_what_it_holds_before_it_listens(text, no_dispatch,
                                                        tmp_path):
    """Start-up's cold start: a collection the server already holds pages of
    has its F1 set dispatched before the socket exists; one filled later is
    promoted by its first request, unwarmed, as before."""
    from open_source_search_engine_tpu.serve.server import SearchHTTPServer
    srv = SearchHTTPServer(tmp_path, port=0)
    coll = srv.colldb.get("main")
    docproc.index_batch(coll, list(text["gen"].pages(SEED, 0, 40, text["p"])))
    seen = {}
    real = srv._warm_device

    def spy():
        seen["listening"] = getattr(srv, "_httpd", None) is not None
        real()
        seen["warmed"] = coll._device_index._f1_warmed
    srv._warm_device = spy
    srv.start()
    try:
        assert seen == {"listening": False, "warmed": True}
        assert {b for _, b in no_dispatch} == set(
            coll._device_index.f1_programs())
    finally:
        srv.stop()


def test_query_rule_blocks_classes_and_uniqueness(text):
    rule, qp, cls = text["rule"], text["qp"], text["cls"]
    queries, cats = text["queries"], text["cats"]
    want = Counter(dict(qp["block"]))
    assert sum(want.values()) == 20
    for lo in range(0, len(queries), 20):
        assert Counter(cats[lo:lo + 20]) == want, lo
    assert len(set(queries)) == len(queries)
    # every term lies in its class by the corpus's own document frequency
    df = text["gen"].doc_freq(text["lens"], text["ids"], text["p"])
    share = df / DOCS
    stop = set(np.argsort(-df, kind="stable")[:CLASSES["stop_words"]])
    for w in cls["High"]:
        assert share[w] >= CLASSES["high_min"] and w not in stop
    for k in ("med", "low"):
        ws = cls[k.capitalize()]
        assert len(ws) and ((share[ws] >= CLASSES[k][0])
                            & (share[ws] <= CLASSES[k][1])).all()
    # a longer list of one seed starts with the shorter one
    assert rule.make(SEED, 60, qp) == queries[:60]
    # a class that runs out ends set-up loudly
    few = json.loads(json.dumps(qp))
    few["classes"]["high_min"] = 0.9
    with pytest.raises(RuntimeError, match="no unseen query"):
        rule.make(SEED, 200, few)


def test_route_counters_count_what_route_counts_counts(text):
    di = engine.get_device_index(text["coll"])
    before = {r: _count(f"devindex.route.{r}") for r in ("f1", "fd", "f2")}
    was = dict(di.route_counts)
    slots0 = {k: v for k, v in g_stats.snapshot()["counters"].items()
              if k.startswith("devindex.program_slot.")}
    keys0 = dict(di.dispatches)
    engine.search_device_batch(text["coll"], text["queries"][:40], topk=10,
                               with_snippets=False)
    moved = {r: di.route_counts[r] - was[r] for r in was}
    assert sum(moved.values()) == 40 and moved["f1"] > 0
    assert moved == {r: _count(f"devindex.route.{r}") - before[r]
                     for r in before}
    # ... and the slot counters tell how many programs the batch rode
    slots = {k: v for k, v in g_stats.snapshot()["counters"].items()
             if k.startswith("devindex.program_slot.")}
    rode = sum(1 for k, n in di.dispatches.items() if n != keys0.get(k, 0))
    assert sum(1 for k, v in slots.items() if v != slots0.get(k, 0)) == rode
