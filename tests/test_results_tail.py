"""The batch path's results tail reads titledb only for rows the answer
shows (``engine.search_device_batch`` -> ``build_results`` ->
``finish_page``): the rerank prefix is as long as the collection's
rerank, the site hashes of a query's candidates come as one column.

The oracle is the tail as it stood: ``build_results`` with no conf in
reach (every rank under ``PQR_SCAN`` fetched) and ``sitehash_of`` asked
docid by docid, over the same raw wave results.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query import engine
from open_source_search_engine_tpu.query.compiler import compile_query
from open_source_search_engine_tpu.utils import ghash
from open_source_search_engine_tpu.utils.stats import g_stats

READER = (Path(__file__).resolve().parents[1] / "benchmarks"
          / "layer_metrics" / "tail_fetches.py")


def _page(title, body):
    return (f"<html><head><title>{title}</title></head>"
            f"<body><p>{body}</p></body></html>")


@pytest.fixture(scope="module")
def coll(tmp_path_factory):
    """``orchard``: 12 sites of 4 pages (clustering hides two a site)
    and, best of all, a pair of equal-content pages on sites of their
    own. ``meadow``: 70 pages, a site each, every content its own: more
    than ``PQR_SCAN`` results survive the walk. Scores differ page by
    page (the padding thins the match out)."""
    c = Collection("tail", tmp_path_factory.mktemp("tail"))
    for i in range(48):
        docproc.index_document(
            c, f"http://grove{i % 12}.test/tree{i}",
            _page(f"Orchard tree {i}",
                  f"orchard harvest notes row{i} {'filler ' * (i + 3)}end"))
    for site in ("twin-a", "twin-b"):
        docproc.index_document(
            c, f"http://{site}.test/same",
            _page("Orchard orchard", "orchard orchard orchard press"))
    for i in range(70):
        docproc.index_document(
            c, f"http://field{i}.test/m",
            _page(f"Meadow plot {i}",
                  f"meadow survey plot{i} {'grass ' * (i + 2)}end"))
    c.dump_all()
    return c


@pytest.fixture()
def pqr(coll, request):
    was = coll.conf.pqr_enabled
    coll.conf.pqr_enabled = request.param
    yield request.param
    coll.conf.pqr_enabled = was


def _present_tail(coll, q, offset, topk=10):
    """The tail as it stood, over the wave's own raw results."""
    di = engine.get_device_index(coll)
    plan = compile_query(q)
    docids, scores, n_matched = di.search_batch(
        [plan], topk=max((topk + offset) * 2, 64))[0]

    def get_doc(d):
        return docproc.get_document(coll, docid=int(d))

    results, clustered = engine.build_results(
        get_doc, docids, scores, plan,
        topk=max(topk + offset, engine.PQR_SCAN), with_snippets=False,
        site_cluster=True, site_of=di.sitehash_of, page=(offset, topk))
    page = engine.finish_page(
        results, offset=offset, topk=topk, conf=coll.conf,
        qlang=plan.lang, langid_of=di.langid_of, get_doc=get_doc,
        words=plan.match_words())
    return page, clustered, n_matched


def _rows(results):
    return [(r.docid, r.score, r.url, r.title, r.snippet, r.site)
            for r in results]


@pytest.mark.parametrize("q", ["orchard", "meadow"])
@pytest.mark.parametrize("offset", [0, 10])
@pytest.mark.parametrize("pqr", [False, True], indirect=True)
def test_the_answer_is_the_present_tails(coll, pqr, offset, q):
    want, clustered, n_matched = _present_tail(coll, q, offset)
    got = engine.search_device(coll, q, topk=10, offset=offset)
    assert len(want) == 10 and all(r.url and r.snippet for r in want)
    assert _rows(got.results) == _rows(want)
    assert (got.total_matches, got.clustered) == (n_matched, clustered)
    if q == "orchard":
        # the fixture is what it says: two a site hidden, one twin too
        assert clustered >= 12 and n_matched == 50
        if not offset:
            assert sum("/same" in r.url for r in got.results) == 1


@pytest.mark.parametrize("offset", [0, 10])
@pytest.mark.parametrize("pqr", [False, True], indirect=True)
def test_a_row_is_fetched_only_where_the_answer_uses_it(
        coll, pqr, offset, monkeypatch):
    """70 matches, 48 ranks walked: with the rerank off the page and
    what precedes it are read and the rest are gap rows; with it on the
    whole window is. Counted at titledb's door and by the counters."""
    calls = []
    real = docproc.get_document

    def spy(c, url=None, docid=None):
        calls.append(docid)
        return real(c, url=url, docid=docid)

    monkeypatch.setattr(docproc, "get_document", spy)
    before = dict(g_stats.snapshot()["counters"])
    res = engine.search_device(coll, "meadow", topk=10, offset=offset)
    after = g_stats.snapshot()["counters"]
    assert res.total_matches == 70 and len(res.results) == 10
    fetched = engine.PQR_SCAN if pqr else offset + 10
    assert len(calls) == len(set(calls)) == fetched
    assert (after["query.titlerec_fetch"]
            - before.get("query.titlerec_fetch", 0)) == fetched
    assert (after.get("query.gap_row", 0)
            - before.get("query.gap_row", 0)) == engine.PQR_SCAN - fetched


def test_site_column_agrees_with_sitehash_of(tmp_path):
    c = Collection("col", tmp_path)
    for i in range(6):
        docproc.index_document(
            c, f"http://s{i % 3}.test/p{i}",
            _page(f"Page {i}", f"column words number{i}"))
    c.dump_all()
    di = engine.get_device_index(c)
    fresh = "http://s9.test/fresh"
    docproc.index_document(c, fresh, _page("Fresh", "column arrival"))
    di.refresh()
    delta = ghash.doc_id(fresh)
    lacking = int(di.all_docids.max()) + 12345
    assert delta in set(di.all_docids.tolist())
    docids = np.array(di.all_docids.tolist()[::-1] + [lacking, delta],
                      np.uint64)
    col = engine.site_column(di, docids)
    assert col == [di.sitehash_of(int(d)) for d in docids]
    assert all(type(h) is int for h in col)
    assert col[-2] == 0 and col[-1] != 0 and len(set(col)) == 5
    assert engine.site_column(di, np.zeros(0, np.uint64)) == []


@pytest.mark.parametrize("counters,want", [
    ({"query": 40.0, "query.titlerec_fetch": 380.0}, 9.5),
    ({"query": 40.0, "query.titlerec_fetch": 0.0}, 0.0),
    ({"query": 40.0}, None),           # the parent: no such counter
    ({"query.titlerec_fetch": 0.0}, None),
])
def test_tail_fetches_reads_the_counter_or_nothing(counters, want):
    spec = importlib.util.spec_from_file_location("tail_fetches", READER)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"counters": counters}) == want
