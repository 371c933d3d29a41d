"""Device-resident index tests — bit-parity with the host-packed path.

The resident kernel reuses score_cube, so any ranking difference means
the gather/rank/scatter front end diverged from the packer's. Every
query family must produce identical (docid, score) sets both ways.
"""

import numpy as np
import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query import engine
from open_source_search_engine_tpu.query.engine import (
    get_device_index, search_device, search_device_batch)

DOCS = {
    "http://a.example.com/fruit": """
      <html><head><title>Fruit basics</title></head><body>
      <h1>Apples and bananas</h1>
      <p>The apple is sweet. A banana is tropical. Apple pie wins.</p>
      </body></html>""",
    "http://b.example.com/apple": """
      <html><head><title>Apple orchard</title></head><body>
      <p>Our orchard grows apple trees. Apple harvest is in fall.
      No banana here.</p></body></html>""",
    "http://c.example.org/banana": """
      <html><head><title>Banana farm</title></head><body>
      <p>Banana plantations export banana bunches worldwide.</p>
      </body></html>""",
    "http://d.example.org/other": """
      <html><head><title>Vegetables</title></head><body>
      <p>Carrots and beets. Root cellar storage tips.</p></body></html>""",
}


@pytest.fixture(scope="module")
def coll(tmp_path_factory):
    c = Collection("dev", tmp_path_factory.mktemp("dev"))
    c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
    for u, h in DOCS.items():
        docproc.index_document(c, u, h)
    return c


QUERIES = ["apple", "banana", "apple banana", "fruit -banana",
           '"apple pie"', "site:b.example.com apple", "zeppelin"]


def assert_parity(host, dev, q):
    """Scores must agree exactly; tied docids may differ (both paths
    return SOME k of the tied docs — tie order is not part of the
    contract, matching TopTree's arbitrary insertion order)."""
    assert dev.total_matches == host.total_matches, q
    assert [round(r.score, 3) for r in dev.results] == \
           [round(r.score, 3) for r in host.results], q
    host_by_score = {}
    for r in host.results:
        host_by_score.setdefault(round(r.score, 3), set()).add(r.docid)
    uniq = {s_ for s_, ds in host_by_score.items() if len(ds) == 1}
    for r in dev.results:
        if round(r.score, 3) in uniq:
            assert {r.docid} == host_by_score[round(r.score, 3)], q
    assert len({r.docid for r in dev.results}) == len(dev.results), q


class TestResidentParity:
    def test_matches_host_packed_path(self, coll):
        for q in QUERIES:
            host = engine.search(coll, q, topk=10, site_cluster=False)
            dev = search_device(coll, q, topk=10, site_cluster=False)
            assert dev.total_matches == host.total_matches, q
            key = lambda r: (-round(r.score, 3), r.docid)
            assert sorted(map(key, dev.results)) == \
                   sorted(map(key, host.results)), q

    def test_batch_matches_single(self, coll):
        batch = search_device_batch(coll, QUERIES, topk=10,
                                    site_cluster=False)
        for q, b in zip(QUERIES, batch):
            s = search_device(coll, q, topk=10, site_cluster=False)
            assert [r.docid for r in b.results] == \
                   [r.docid for r in s.results], q
            np.testing.assert_allclose(
                [r.score for r in b.results],
                [r.score for r in s.results], rtol=1e-6)

    def test_dispatches_are_counted_by_program_and_bucket(self, coll):
        """``_costed`` is the one door every wave program goes
        through: the index's own count by (program name, shape bucket)
        is the sum of what it dispatched, and the signature the
        benchmark's deployment file checks stays as it was."""
        import inspect
        di = get_device_index(coll)
        assert list(inspect.signature(di._costed.__func__).parameters
                    )[:5] == ["self", "name", "bucket", "modeled_bytes",
                              "fn"]
        before = dict(di.dispatches)
        search_device(coll, "apple", topk=10)
        search_device(coll, "apple", topk=10)
        new = {k: n - before.get(k, 0) for k, n in di.dispatches.items()
               if n != before.get(k, 0)}
        assert list(new.values()) == [2], new
        ((name, bucket),) = new
        assert name == "devindex._two_phase"
        assert len(bucket) == 6 and all(isinstance(x, int)
                                        for x in bucket)

    def test_refresh_tracks_writes(self, coll):
        di = get_device_index(coll)
        v0 = di._built_version
        assert not search_device(coll, "quokka").results
        docproc.index_document(
            coll, "http://e.example.org/q",
            "<html><title>Q</title><body>a quokka appears</body></html>")
        res = search_device(coll, "quokka")
        assert get_device_index(coll)._built_version > v0
        assert len(res.results) == 1
        docproc.remove_document(coll, "http://e.example.org/q")
        assert not search_device(coll, "quokka").results

    def test_empty_collection(self, tmp_path):
        c = Collection("empty", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        assert search_device(c, "anything").total_matches == 0

    def test_pure_negative_query_matches_host(self, coll):
        """`-apple` must match NOTHING on both paths (the reference's
        early-out when no positive required term exists) — the resident
        path used to match every doc lacking the term."""
        host = engine.search(coll, "-apple", topk=10)
        dev = search_device(coll, "-apple", topk=10)
        assert host.total_matches == 0 and not host.results
        assert dev.total_matches == 0 and not dev.results

    def test_over_quota_occurrences_keep_sibling_sublists(self, tmp_path):
        """A doc with more than quota (P//n_sublists) occurrences of a
        word must not clobber its bigram sublist's slots: over-quota
        scatter lanes are routed to the drop row (duplicate-index
        scatter order is implementation-defined on TPU)."""
        c = Collection("quota", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        spam = " ".join(["pepper"] * 24) + " pepper mill grinder."
        docproc.index_document(
            c, "http://q.example.com/mill",
            f"<html><head><title>Mill</title></head><body><p>{spam}</p>"
            "</body></html>")
        docproc.index_document(
            c, "http://q.example.com/other",
            "<html><head><title>Other</title></head><body>"
            "<p>salt mill only here.</p></body></html>")
        for q in ["pepper mill", "pepper", '"pepper mill"']:
            host = engine.search(c, q, topk=10, site_cluster=False)
            dev = search_device(c, q, topk=10, site_cluster=False)
            assert dev.total_matches == host.total_matches, q
            key = lambda r: (-round(r.score, 3), r.docid)
            assert sorted(map(key, dev.results)) == \
                   sorted(map(key, host.results)), q


class TestScale:
    """The round-2 scale contract: runs longer than any fixed cap score
    fully (docid-tile streaming), identical to the host-packed path."""

    def test_large_termlist_no_truncation(self, tmp_path):
        import numpy as np

        from open_source_search_engine_tpu.index import posdb
        from open_source_search_engine_tpu.utils import ghash

        c = Collection("big", tmp_path)

        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        n = 40_000  # > the old 32768-per-run resident cap
        docids = np.arange(1, n + 1, dtype=np.uint64)
        common = ghash.term_id("common")
        rare = ghash.term_id("rare")
        keys = [posdb.pack(termid=common, docid=docids, wordpos=5,
                           densityrank=10, siterank=docids % 15,
                           hashgroup=0, langid=1)]
        keys.append(posdb.pack(termid=rare, docid=docids[::200], wordpos=9,
                               densityrank=10, siterank=docids[::200] % 15,
                               hashgroup=0, langid=1))
        c.posdb.add(np.concatenate(keys))
        c.num_docs = n

        host = engine.search(c, "common rare", topk=10,
                             with_snippets=False, site_cluster=False)
        dev = search_device(c, "common rare", topk=10,
                            with_snippets=False, site_cluster=False)
        assert host.total_matches == len(docids[::200])
        assert dev.total_matches == host.total_matches
        # identical postings per doc → massive score ties: the two
        # paths may legitimately return different tie members, so pin
        # the score sequence (the tie-aware parity contract)
        assert [round(r.score, 3) for r in dev.results] == \
               [round(r.score, 3) for r in host.results]

        # single common term: every doc matches, none truncated away.
        # Scores tie massively (identical postings), so the two paths
        # may pick different — equally best — docids: compare scores,
        # not the arbitrary tie order.
        host1 = engine.search(c, "common", topk=10, with_snippets=False,
                              site_cluster=False)
        dev1 = search_device(c, "common", topk=10, with_snippets=False,
                             site_cluster=False)
        assert host1.total_matches == n
        assert dev1.total_matches == n
        assert [round(r.score, 3) for r in dev1.results] == \
               [round(r.score, 3) for r in host1.results]
        assert len({r.docid for r in dev1.results}) == 10
        assert all(r.docid in set(docids) for r in dev1.results)


class TestIncrementalDelta:
    """Adds/deletes against a served index cost O(memtable), not
    O(corpus): the base rebuilds only when the Rdb run set moves."""

    def test_adds_and_deletes_without_full_rebuild(self, tmp_path):
        c = Collection("inc", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        for i in range(30):
            docproc.index_document(
                c, f"http://inc.test/d{i}",
                f"<html><head><title>Doc {i}</title></head><body>"
                f"<p>stable corpus text number{i} here.</p></body></html>")
        c.posdb.dump()  # base postings now live in a run
        di = get_device_index(c)
        base_rebuilds = di.full_rebuilds

        # adds land in the delta: visible immediately, no base rebuild
        for i in range(3):
            docproc.index_document(
                c, f"http://inc.test/new{i}",
                "<html><head><title>Fresh</title></head><body>"
                f"<p>freshterm arrives number{i} stable.</p></body></html>")
            res = search_device(c, "freshterm")
            assert res.total_matches == i + 1
        assert di.full_rebuilds == base_rebuilds
        assert di.delta_rebuilds > 0

        # delete a BASE doc: dead-masked out, still no base rebuild
        assert docproc.remove_document(c, "http://inc.test/d5")
        res = search_device(c, "number5")
        assert all("d5" not in r.url for r in res.results)
        assert search_device(c, "stable").total_matches == 32
        assert di.full_rebuilds == base_rebuilds

        # re-index a base doc with new content: old postings dead,
        # new postings served from the delta
        docproc.index_document(
            c, "http://inc.test/d7",
            "<html><head><title>Doc 7 v2</title></head><body>"
            "<p>rewrittenterm stable now.</p></body></html>")
        assert search_device(c, "rewrittenterm").total_matches == 1
        assert search_device(c, "number7").total_matches == 0
        assert di.full_rebuilds == base_rebuilds

        # parity with the host path across the mixed base/delta state
        for q in ["stable", "freshterm", "rewrittenterm", "number12"]:
            host = engine.search(c, q, topk=10, site_cluster=False)
            dev = search_device(c, q, topk=10, site_cluster=False)
            assert_parity(host, dev, q)

        # a dump moves the run set: a BACKGROUND rebuild folds it into
        # a fresh index while the old one keeps serving, then swaps
        c.posdb.dump()
        search_device(c, "stable")  # never blocks on the rebuild
        import time as _t
        for _ in range(100):
            if get_device_index(c) is not di:
                break
            _t.sleep(0.1)
        di2 = get_device_index(c)
        assert di2 is not di and di2.full_rebuilds == 1
        assert search_device(c, "stable").total_matches > 0

    def test_identical_recrawl_no_double_serving(self, tmp_path):
        """Re-indexing a doc with UNCHANGED content (routine recrawl):
        the tombstone/positive pairs annihilate inside the memtable, so
        no tombstone survives — the base copy must still be superseded
        or the doc serves from both base and delta with doubled df."""
        c = Collection("recrawl", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        html = ("<html><head><title>Evergreen</title></head><body>"
                "<p>evergreen content never changes.</p></body></html>")
        docproc.index_document(c, "http://re.test/page", html)
        docproc.index_document(
            c, "http://re.test/other",
            "<html><head><title>Other</title></head><body>"
            "<p>different content here.</p></body></html>")
        c.posdb.dump()
        get_device_index(c)
        # identical re-index: base copy superseded, delta serves
        docproc.index_document(c, "http://re.test/page", html)
        host = engine.search(c, "evergreen content", topk=10,
                             site_cluster=False)
        dev = search_device(c, "evergreen content", topk=10,
                            site_cluster=False)
        assert host.total_matches == 1
        assert dev.total_matches == 1
        assert round(dev.results[0].score, 3) == \
               round(host.results[0].score, 3)


class TestFullCubePath:
    """F2 routing: corpus-wide drivers score on the full-cube kernel —
    results must match the host-packed path exactly (same min_scores)."""

    def test_f2_parity_with_host(self, tmp_path, monkeypatch):
        import open_source_search_engine_tpu.query.devindex as dv

        # shrink thresholds so a 200-doc corpus exercises dense rows,
        # materialized cube rows, AND the F2 route
        monkeypatch.setattr(dv, "DENSE_MIN_DF", 0)
        monkeypatch.setattr(dv, "CUBE_MIN_DF", 16)
        c = Collection("f2", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        for i in range(200):
            extra = "orange grove" if i % 3 == 0 else "plain field"
            docproc.index_document(
                c, f"http://f2.test/s{i % 7}/d{i}",
                f"<html><head><title>Doc {i} common</title></head><body>"
                f"<p>common words everywhere {extra} number{i}.</p>"
                "</body></html>")
        c.posdb.dump()
        # delta postings on top of the base (tests the scatter rows)
        docproc.index_document(
            c, "http://f2.test/fresh",
            "<html><head><title>Fresh common</title></head><body>"
            "<p>common orange arrival.</p></body></html>")
        di = get_device_index(c)

        queries = ["common", "common words", "common orange",
                   '"common words"', "common -orange", "words everywhere"]
        for q in queries:
            host = engine.search(c, q, topk=10, site_cluster=False,
                                 with_snippets=False)
            dev = search_device(c, q, topk=10, site_cluster=False,
                                with_snippets=False)
            assert_parity(host, dev, q)
        # the common-word queries really did take the F2 route
        p = di.plan(
            __import__("open_source_search_engine_tpu.query.compiler",
                       fromlist=["compile_query"]).compile_query("common"))
        assert p.driver_df > dv.CUBE_MIN_DF
        assert len(di.cube_slot_of) > 0  # cube rows materialized


    def test_fd_direct_route_parity(self, tmp_path, monkeypatch):
        """The direct-cube (FD) kernel: all-cube-term queries skip cube
        assembly; results must match the host path exactly, and the
        route must actually be taken (direct_ok) until delta postings
        disqualify it."""
        import open_source_search_engine_tpu.query.devindex as dv
        from open_source_search_engine_tpu.query.compiler import \
            compile_query

        monkeypatch.setattr(dv, "DENSE_MIN_DF", 0)
        monkeypatch.setattr(dv, "CUBE_MIN_DF", 16)
        c = Collection("fd", tmp_path)
        c.conf.pqr_enabled = False
        for i in range(200):
            extra = "orange grove" if i % 3 == 0 else "plain field"
            docproc.index_document(
                c, f"http://fd.test/s{i % 7}/d{i}",
                f"<html><head><title>Doc {i} common</title></head><body>"
                f"<p>common words everywhere {extra} number{i}.</p>"
                "</body></html>")
        c.posdb.dump()
        di = get_device_index(c)
        queries = ["common", "common words", "words everywhere common"]
        for q in queries:
            p = di.plan(compile_query(q))
            assert p.direct_ok, q  # base-only cube terms -> FD route
            host = engine.search(c, q, topk=10, site_cluster=False,
                                 with_snippets=False)
            dev = search_device(c, q, topk=10, site_cluster=False,
                                with_snippets=False)
            assert_parity(host, dev, q)
        # delta postings ride the FD scatter tail (still direct);
        # parity must hold through it
        docproc.index_document(
            c, "http://fd.test/fresh",
            "<html><head><title>Fresh common</title></head><body>"
            "<p>common arrival.</p></body></html>")
        di.refresh()
        p = di.plan(compile_query("common"))
        assert p.direct_ok and len(p.p_start)  # delta -> scatter rows
        host = engine.search(c, "common", topk=10, site_cluster=False,
                             with_snippets=False)
        dev = search_device(c, "common", topk=10, site_cluster=False,
                            with_snippets=False)
        assert_parity(host, dev, "common")


class TestClusterdbRead:
    """Query-time clusterdb use (Clusterdb.h:42, Msg51.h:96): the
    sitehash column clusters results BEFORE any titledb access."""

    def test_sitehash_clustering_matches_titlerec_clustering(self, coll):
        di = get_device_index(coll)
        # sitehashes exist for every doc and group by site
        a = di.sitehash_of(
            __import__("open_source_search_engine_tpu.utils.ghash",
                       fromlist=["doc_id"]).doc_id(
                "http://a.example.com/fruit"))
        assert a != 0
        host = engine.search(coll, "apple", topk=10, site_cluster=True)
        dev = search_device(coll, "apple", topk=10, site_cluster=True)
        assert {r.url for r in dev.results} == {r.url for r in host.results}
        assert dev.clustered == host.clustered

    def test_hidden_results_skip_titledb(self, tmp_path):
        c = Collection("clu", tmp_path)
        c.conf.pqr_enabled = False  # kernel-parity tests pin pre-PQR scores
        for i in range(6):
            docproc.index_document(
                c, f"http://one.site.test/p{i}",
                f"<html><head><title>Page {i} shared</title></head>"
                f"<body><p>shared words everywhere {i}.</p></body></html>")
        fetched = []
        orig = docproc.get_document

        def spy(coll_, url=None, docid=None):
            fetched.append(docid)
            return orig(coll_, url=url, docid=docid)

        import open_source_search_engine_tpu.query.engine as eng
        di = get_device_index(c)
        raw = di.search_batch(["shared"], topk=64)
        from open_source_search_engine_tpu.query.compiler import (
            compile_query)
        docids, scores, nm = raw[0]
        results, clustered = eng.build_results(
            lambda d: spy(c, docid=d), docids, scores,
            compile_query("shared"), topk=10, with_snippets=False,
            site_cluster=True, site_of=di.sitehash_of)
        assert nm == 6 and clustered == 4
        assert len(results) == 2
        # only the 2 served results touched titledb — the 4 hidden by
        # clustering were decided from the clusterdb sitehash column
        assert len(fetched) == 2


class TestBackgroundRebase:
    def test_dump_does_not_block_serving(self, tmp_path, monkeypatch):
        """A run-set move (dump) must not block queries: the old
        resident view keeps serving (VERDICT r3 item 6; reference
        RdbDump.h:21 — dumps never block the loop) while the rebuild
        runs in the background, then the new base swaps in."""
        import threading
        import time as _time

        import open_source_search_engine_tpu.query.devindex as dv
        from open_source_search_engine_tpu.query.engine import \
            get_device_index

        c = Collection("bg", tmp_path)
        c.conf.pqr_enabled = False
        for i in range(30):
            docproc.index_document(
                c, f"http://bg.test/d{i}",
                f"<html><body><p>resident words number{i}</p></body>"
                "</html>")
        di0 = get_device_index(c)
        r0 = search_device(c, "resident", topk=5, with_snippets=False)
        assert r0.total_matches == 30

        # make the rebuild observably slow
        gate = threading.Event()
        orig = dv.DeviceIndex._build_base

        def slow_build(self, *a, **kw):
            gate.wait(10.0)
            return orig(self, *a, **kw)

        monkeypatch.setattr(dv.DeviceIndex, "_build_base", slow_build)
        docproc.index_document(
            c, "http://bg.test/fresh",
            "<html><body><p>resident fresh arrival</p></body></html>")
        c.posdb.dump()  # run set moves -> background rebuild

        t0 = _time.perf_counter()
        r1 = search_device(c, "resident", topk=5, with_snippets=False)
        blocked = _time.perf_counter() - t0
        assert blocked < 5.0          # did NOT wait for the rebuild
        assert r1.total_matches == 30  # frozen pre-dump view serves
        assert get_device_index(c) is di0

        gate.set()  # let the rebuild finish, then poll for the swap
        for _ in range(100):
            if get_device_index(c) is not di0:
                break
            _time.sleep(0.1)
        di1 = get_device_index(c)
        assert di1 is not di0
        r2 = search_device(c, "resident", topk=5, with_snippets=False)
        assert r2.total_matches == 31  # the dumped write is visible
