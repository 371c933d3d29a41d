"""Chaos plane — deterministic fault injection and deadline propagation.

The contract under test (utils/chaos.py + utils/deadline.py):

* off is a true no-op and armed schedules are pure functions of
  ``(seed, point, call#)`` — same seed, same fault sequence;
* a refused scatter leg fast-fails to the twin (no connect-timeout
  ride-out) and takes the dead twin out of rotation at once;
* a query's deadline travels serve edge → scatter leg header → node
  dequeue → device dispatch / resident issue, and each checkpoint
  abandons (counted) instead of burning work nobody waits for;
* expired queries serve the cache plane's just-stale answer marked
  degraded before they refuse, and degraded SERPs are never cached;
* a killed primary mid-query is eaten by the hedge, and the dead
  twin's penalty decays once it answers pings again;
* flipped bytes in a posting run trip CRC quarantine — detected,
  never served.
"""

import threading
import time

import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.parallel import cluster as cl
from open_source_search_engine_tpu.query.engine import (
    _compile_cached, get_resident_loop, search_device_batch)
from open_source_search_engine_tpu.serve.server import (QueryBatcher,
                                                        SearchHTTPServer)
from open_source_search_engine_tpu.utils import chaos as chaos_mod
from open_source_search_engine_tpu.utils import deadline as deadline_mod
from open_source_search_engine_tpu.utils import ghash
from open_source_search_engine_tpu.utils.chaos import (DEFAULT_POINTS,
                                                       ChaosError,
                                                       ChaosPlane,
                                                       g_chaos)
from open_source_search_engine_tpu.utils.deadline import (Deadline,
                                                          DeadlineExceeded)
from open_source_search_engine_tpu.utils.membudget import MemBudget
from open_source_search_engine_tpu.utils.stats import g_stats
from open_source_search_engine_tpu.utils.trace import g_tracer

from .polling import wait_until


@pytest.fixture(autouse=True)
def _chaos_reset():
    """Every test starts unarmed with clean counters and leaves the
    process-global plane unarmed (the OSSE_CHAOS-unset no-op that the
    rest of the suite relies on)."""
    g_chaos.disable()
    g_stats.reset()
    yield
    g_chaos.disable()


def _count(name: str) -> int:
    return g_stats.snapshot()["counters"].get(name, 0)


def _await_count(name: str, n: int = 1, timeout: float = 5.0) -> int:
    """Counters bumped on server/background threads land a beat after
    the client call returns — poll instead of asserting a race."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        c = _count(name)
        if c >= n:
            return c
        time.sleep(0.01)
    return _count(name)


def _doc(i, words="cluster shared words"):
    return (f"<html><head><title>Doc {i}</title></head><body>"
            f"<p>{words} token{i}.</p></body></html>")


# ---------------------------------------------------------------------------
# the plane itself: determinism, aiming, arming
# ---------------------------------------------------------------------------

class TestChaosPlane:
    def test_off_is_noop(self):
        p = ChaosPlane()
        assert not p.enabled
        assert all(p.decide(pt) is None for pt in DEFAULT_POINTS)
        # the global plane ships unarmed — the single-flag-check no-op
        # every hot-path seam guards on
        assert g_chaos.enabled is False

    def test_same_seed_replays_same_schedule(self):
        p = ChaosPlane()
        p.enable(42, rate=0.5)
        seq1 = [p.decide("transport.request") for _ in range(64)]
        p.enable(42, rate=0.5)  # re-arm resets the call counters
        seq2 = [p.decide("transport.request") for _ in range(64)]
        assert seq1 == seq2
        assert any(k is not None for k in seq1)  # rate=0.5 fires some
        assert any(k is None for k in seq1)      # ...and skips some
        p.enable(43, rate=0.5)
        seq3 = [p.decide("transport.request") for _ in range(64)]
        assert seq3 != seq1  # a different seed is a different schedule
        p.disable()
        assert p.decide("transport.request") is None

    def test_match_filter_aims_without_skewing_the_schedule(self):
        # the match filter applies AFTER the call counter bump, so an
        # aimed plane and an unaimed one stay call-for-call aligned
        p, q = ChaosPlane(), ChaosPlane()
        p.enable(7, rate=1.0)
        q.enable(7, rate=1.0)
        q.configure("transport.request", match="10.0.0.9:8042")
        keys = ["10.0.0.9:8042/rpc/search", "10.0.0.7:8042/rpc/search",
                "10.0.0.9:8042/rpc/doc", "10.0.0.8:8042/rpc/search"]
        for k in keys:
            kind_all = p.decide("transport.request", key=k)
            kind_aimed = q.decide("transport.request", key=k)
            if "10.0.0.9:8042" in k:
                assert kind_aimed == kind_all
            else:
                assert kind_aimed is None

    def test_configure_narrows_kinds_and_rate(self):
        p = ChaosPlane()
        p.enable(5, rate=0.0)  # armed, but every point quiet...
        assert p.decide("transport.request") is None
        p.configure("transport.request", rate=1.0, kinds=("refuse",))
        assert all(p.decide("transport.request") == "refuse"
                   for _ in range(10))
        # ...and the other points stayed quiet
        assert p.decide("cluster.node") is None
        assert p.fired("transport.request")["refuse"] == 10

    def test_maybe_enable_env(self, monkeypatch):
        monkeypatch.delenv("OSSE_CHAOS", raising=False)
        assert chaos_mod.maybe_enable() is False
        monkeypatch.setenv("OSSE_CHAOS", "not-a-seed")
        assert chaos_mod.maybe_enable() is False
        assert not g_chaos.enabled
        monkeypatch.setenv("OSSE_CHAOS", "7")
        assert chaos_mod.maybe_enable() is True
        assert g_chaos.enabled and g_chaos.seed == 7


# ---------------------------------------------------------------------------
# the Deadline helper
# ---------------------------------------------------------------------------

class TestDeadline:
    def test_budget_arithmetic_and_header_roundtrip(self):
        dl = Deadline.after(5.0)
        assert 0.0 < dl.remaining() <= 5.0
        assert not dl.expired()
        assert dl.clamp(10.0) <= 5.0
        assert dl.clamp(0.001) == pytest.approx(0.001, abs=1e-3)
        # the wire carries remaining BUDGET, not a wall-clock instant
        dl2 = Deadline.from_header(dl.header_value())
        assert abs(dl2.remaining() - dl.remaining()) < 0.1
        assert Deadline.from_header(None) is None
        assert Deadline.from_header("") is None
        assert Deadline.from_header("bogus") is None
        gone = Deadline.after(-1.0)
        assert gone.expired() and gone.clamp(10.0) == 0.0
        assert gone.header_value() == "0.0000"

    def test_check_abandon_counts_and_binds(self):
        # nothing bound: unbudgeted work never abandons
        assert deadline_mod.current() is None
        assert not deadline_mod.check_abandon("nowhere")
        with deadline_mod.bind(Deadline.after(60.0)):
            assert not deadline_mod.check_abandon("early")
            deadline_mod.note_met()
        assert _count("deadline.met") == 1
        with deadline_mod.bind(Deadline.after(-1.0)):
            assert deadline_mod.check_abandon("spot")
        assert deadline_mod.current() is None
        assert _count("deadline.abandoned") == 1
        assert _count("deadline.abandoned.spot") == 1

    def test_query_batcher_deadline_beats_own_timeout(self):
        ev = threading.Event()

        def run_batch(key, qs):
            ev.wait(timeout=2.0)
            return [f"r:{q}" for q in qs]

        qb = QueryBatcher(run_batch)
        try:
            with deadline_mod.bind(Deadline.after(0.05)):
                with pytest.raises(DeadlineExceeded):
                    qb.search(("main", 10, 0), "slow question",
                              timeout=30.0)
            ev.set()
            # an unbudgeted rider on the same batcher still completes
            assert qb.search(("main", 10, 0), "fine") == "r:fine"
        finally:
            ev.set()
            qb.stop()


# ---------------------------------------------------------------------------
# transport chaos: fast-fail on refusal (satellite: dead-peer fast-fail)
# ---------------------------------------------------------------------------

class TestTransportChaos:
    def test_refused_primary_fastfails_to_twin(self, tmp_path):
        a = cl.ShardNodeServer(tmp_path / "a", port=0)
        b = cl.ShardNodeServer(tmp_path / "b", port=0)
        for n in (a, b):  # twins carry the same docs
            for i in range(4):
                n.handle("/rpc/index", {"url": f"http://t.test/d{i}",
                                        "content": _doc(i)})
            n.start()
        conf = cl.HostsConf.parse(
            f"num-mirrors: 1\n127.0.0.1:{a.port}\n127.0.0.1:{b.port}")
        client = cl.ClusterClient(conf, use_heartbeat=False)
        client.hostmap.rtt_s[0, 0] = 0.001  # pin a as primary
        client.hostmap.rtt_s[0, 1] = 0.002
        try:
            g_chaos.enable(11, rate=0.0)
            g_chaos.configure("transport.request", rate=1.0,
                              kinds=("refuse",),
                              match=f"127.0.0.1:{a.port}")
            res = client.search("cluster shared", topk=5)
            # the twin answered in full — no degraded partial, and the
            # refusal cost no connect-timeout ride-out
            assert res.total_matches > 0 and res.results
            assert not res.degraded
            assert _count("transport.fastfail") >= 1
            # actively refused = known dead right now: out of rotation
            # immediately, no ping grace
            assert not client.hostmap.alive[0, 0]
            assert client.hostmap.twin_order(0)[0] == 1
        finally:
            g_chaos.disable()
            client.close()
            a.stop()
            b.stop()

    def test_dropped_leg_degrades_partial_and_stays_uncached(
            self, tmp_path):
        """Satellite: a timed-out/dropped scatter leg yields a partial
        answer marked degraded, counted, and never pinned in the result
        cache for a TTL."""
        a = cl.ShardNodeServer(tmp_path / "a", port=0)
        b = cl.ShardNodeServer(tmp_path / "b", port=0)
        a.start()
        b.start()
        conf = cl.HostsConf.parse(
            f"num-mirrors: 0\n127.0.0.1:{a.port}\n127.0.0.1:{b.port}")
        client = cl.ClusterClient(conf, use_heartbeat=False)
        try:
            per_shard = {0: 0, 1: 0}
            for i in range(16):
                url = f"http://t.test/d{i}"
                s = int(client.hostmap.shard_of_docid(ghash.doc_id(url)))
                per_shard[s] += 1
                client.index_document(url, _doc(i))
            assert per_shard[0] and per_shard[1]  # both shards populated
            g_chaos.enable(13, rate=0.0)
            g_chaos.configure("transport.request", rate=1.0,
                              kinds=("drop",),
                              match=f"127.0.0.1:{b.port}")
            res = client.search("cluster shared words", topk=10)
            assert res.degraded  # shard b's leg dropped: partial answer
            assert res.total_matches > 0  # ...but shard a still answered
            assert _count("results.degraded") >= 1
            # the degraded SERP was served once, not cached: the same
            # query recomputes (and degrades again)
            before = _count("results.degraded")
            res2 = client.search("cluster shared words", topk=10)
            assert res2.degraded
            assert _count("results.degraded") > before
        finally:
            g_chaos.disable()
            client.close()
            a.stop()
            b.stop()


# ---------------------------------------------------------------------------
# deadline propagation through the cluster serve path
# ---------------------------------------------------------------------------

class TestDeadlinePropagation:
    def test_expired_deadline_abandons_at_node_dequeue(self, tmp_path):
        node = cl.ShardNodeServer(tmp_path / "n", port=0)
        for i in range(3):
            node.handle("/rpc/index", {"url": f"http://t.test/d{i}",
                                       "content": _doc(i)})
        node.start()
        conf = cl.HostsConf.parse(f"num-mirrors: 0\n127.0.0.1:{node.port}")
        client = cl.ClusterClient(conf, use_heartbeat=False)
        try:
            with deadline_mod.bind(Deadline.after(0.0001)):
                res = client.search("cluster shared", topk=5)
            # the budget was gone before the scatter: partial/empty
            # answer marked degraded, never a hang
            assert res.degraded
            # the node saw the shipped budget and abandoned at the door
            assert _await_count("deadline.abandoned.node.dequeue") >= 1
            assert _count("deadline.abandoned") >= 1
            # a generously budgeted query on the same plane completes
            with deadline_mod.bind(Deadline.after(60.0)):
                res2 = client.search("cluster shared token1", topk=5)
            assert not res2.degraded and res2.total_matches > 0
        finally:
            client.close()
            node.stop()

    def test_expired_deadline_abandons_device_dispatch(self, tmp_path):
        coll = Collection("chaosdev", tmp_path)
        coll.conf.pqr_enabled = False
        for i in range(3):
            docproc.index_document(coll, f"http://d.test/p{i}", _doc(i))
        with deadline_mod.bind(Deadline.after(-1.0)):
            with pytest.raises(DeadlineExceeded):
                search_device_batch(coll, ["cluster"], topk=5)
        assert _count("deadline.abandoned.device.dispatch") >= 1


# ---------------------------------------------------------------------------
# serve edge: stale-before-refuse, degraded SERPs uncached
# ---------------------------------------------------------------------------

@pytest.fixture
def srv(tmp_path):
    s = SearchHTTPServer(tmp_path, port=0)
    coll = s.colldb.get("main")
    for i in range(6):
        docproc.index_document(
            coll, f"http://a{i % 3}.test/p{i}",
            f"<html><title>t{i}</title><body><p>serve corpus words "
            f"number{i}</p></body></html>")
    return s


def _search(s, **q):
    return s.handle("GET", "/search", {k: str(v) for k, v in q.items()},
                    b"")


class TestServeEdge:
    def test_deadline_met_is_counted(self, srv):
        code, body, _ = _search(srv, q="serve corpus",
                                deadline_ms=60000)
        assert code == 200
        assert _count("deadline.met") >= 1

    def test_expired_query_serves_stale_marked_degraded(self, srv):
        coll = srv.colldb.get("main")
        coll.conf.result_cache_ttl = 0.05
        code, page, _ = _search(srv, q="serve corpus")
        assert code == 200  # primed the result cache
        # ...and poll until the entry expires in place (lookup counts
        # the miss without evicting, so lookup_stale still finds it) —
        # a fixed sleep here flakes on loaded boxes
        gen = srv._result_gen(coll)
        ckey = ("main", "serve corpus", 10, 0, "json")
        wait_until(
            lambda: not srv._result_cache.lookup(ckey, gen=gen)[0],
            timeout=2.0, desc="result cache entry expiry")

        def timed_out_render(*a, **kw):
            raise DeadlineExceeded("chaos: render over budget")

        srv._render_search = timed_out_render
        code2, page2, _ = _search(srv, q="serve corpus")
        # just-stale beats refusal: same page, marked served-stale
        assert code2 == 200 and page2 == page
        assert _count("deadline.stale_served") == 1
        assert srv.stats.get("deadline_stale") == 1
        # no stale entry to fall back on → honest refusal
        code3, body3, _ = _search(srv, q="never cached words")
        assert code3 == 504
        assert _count("deadline.refused") == 1

    def test_degraded_serp_never_cached(self, srv):
        coll = srv.colldb.get("main")
        coll.conf.result_cache_ttl = 30.0
        degrade = True

        def render(query, q, n, s, fmt, rc_coll, debug, tr,
                   degraded_out=None):
            if degrade and degraded_out is not None:
                degraded_out["degraded"] = True
            return 200, '{"results": []}', "application/json"

        srv._render_search = render
        gen = srv._result_gen(coll)
        code, _, _ = _search(srv, q="partial words")
        assert code == 200
        hit, _ = srv._result_cache.lookup(
            ("main", "partial words", 10, 0, "json"), gen=gen)
        assert not hit  # a partial answer must not serve for a TTL
        degrade = False
        code, _, _ = _search(srv, q="whole words")
        assert code == 200
        hit, _ = srv._result_cache.lookup(
            ("main", "whole words", 10, 0, "json"), gen=gen)
        assert hit  # the control: complete answers do cache


# ---------------------------------------------------------------------------
# twin failover end-to-end: kill the primary mid-query
# ---------------------------------------------------------------------------

def _span_tags(node, out):
    out.append(node.get("tags", {}))
    for c in node.get("children", []):
        _span_tags(c, out)
    return out


class TestTwinFailover:
    def test_kill_primary_mid_query_hedge_eats_it(self, tmp_path):
        # 2 shards × 2 twins, replica-major host order: a0 b0 a1 b1
        nodes = [cl.ShardNodeServer(tmp_path / nm, port=0)
                 for nm in ("a0", "b0", "a1", "b1")]
        for n in nodes:
            n.start()
        conf = cl.HostsConf.parse(
            "num-mirrors: 1\n" + "\n".join(
                f"127.0.0.1:{n.port}" for n in nodes))
        client = cl.ClusterClient(conf, use_heartbeat=False)
        client.hostmap.rtt_s[:, 0] = 0.001  # replica 0 is primary
        client.hostmap.rtt_s[:, 1] = 0.002
        a0 = nodes[0]
        a0_port = a0.port
        try:
            for i in range(12):  # writes land on every twin of a shard
                client.index_document(f"http://t.test/d{i}", _doc(i))
            g_chaos.enable(17, rate=0.0)
            g_chaos.configure("cluster.node", rate=1.0, kinds=("kill",),
                              match=str(a0_port), delay_s=0.05)
            with g_tracer.start("killquery", sampled=True) as tr:
                res = client.search("cluster shared words", topk=10)
            # the answer is COMPLETE: the killed twin's shard answered
            # through its mirror, nothing degraded, nothing lost
            assert not res.degraded
            assert res.total_matches > 0 and res.results
            assert g_chaos.fired("cluster.node").get("kill", 0) >= 1
            assert _count("transport.hedge_fired") >= 1
            assert _count("transport.hedge_won") >= 1
            # the trace shows the hedge leg winning the race
            tags = _span_tags(tr.export()["root"], [])
            assert any(t.get("hedge") and t.get("won") for t in tags)
            g_chaos.disable()
            # the killed twin (shard 0 replica 0) fell out of
            # preference: its in-flight penalty demoted it
            pen0 = max(float(client.hostmap.rtt_s[s, 0])
                       for s in range(2))
            assert client.hostmap.twin_order(0)[0] == 1
            # ...and a restart + health pings decay the penalty instead
            # of demoting it forever
            a0.stop()  # idempotent: make sure the kill's stop finished
            restarted = cl.ShardNodeServer(tmp_path / "a0",
                                           port=a0_port)
            give_up = Deadline.after(10.0)
            while True:
                try:
                    restarted.start()
                    break
                except OSError:  # socket still draining from the kill
                    if give_up.expired():
                        raise
                    time.sleep(0.05)
            try:
                for _ in range(3):
                    client.check_hosts()
                assert bool(client.hostmap.alive.all())
                pen1 = max(float(client.hostmap.rtt_s[s, 0])
                           for s in range(2))
                assert pen1 < pen0
            finally:
                restarted.stop()
        finally:
            g_chaos.disable()
            client.close()
            for n in nodes[1:]:
                n.stop()


# ---------------------------------------------------------------------------
# resident loop chaos
# ---------------------------------------------------------------------------

DOCS = {
    "http://a.example.com/fruit": """
      <html><head><title>Fruit basics</title></head><body>
      <p>The apple is sweet. A banana is tropical. Apple pie wins.</p>
      </body></html>""",
    "http://b.example.com/apple": """
      <html><head><title>Apple orchard</title></head><body>
      <p>Our orchard grows apple trees. Apple harvest is in fall.</p>
      </body></html>""",
}


@pytest.fixture
def rescoll(tmp_path):
    c = Collection("chaosres", tmp_path)
    c.conf.pqr_enabled = False
    for u, h in DOCS.items():
        docproc.index_document(c, u, h)
    return c


class TestResidentChaos:
    def test_dropped_collect_fails_wave_not_loop(self, rescoll):
        loop = get_resident_loop(rescoll)
        plans = [_compile_cached("apple", 0)]
        g_chaos.enable(23, rate=0.0)
        g_chaos.configure("resident.loop", rate=1.0,
                          kinds=("drop_collect",), match="collect")
        with pytest.raises(ChaosError):
            loop.submit(plans, topk=16, lang=0).wait(timeout=60)
        # the wave died; the loop did not — the next submit answers
        g_chaos.disable()
        ((d, s, n),) = loop.submit(plans, topk=16,
                                   lang=0).wait(timeout=60)
        assert n > 0

    def test_stalled_wave_still_answers(self, rescoll):
        loop = get_resident_loop(rescoll)
        g_chaos.enable(29, rate=0.0)
        g_chaos.configure("resident.loop", rate=1.0, kinds=("stall",),
                          delay_s=0.01)
        ((d, s, n),) = loop.submit([_compile_cached("apple", 0)],
                                   topk=16, lang=0).wait(timeout=60)
        assert n > 0
        assert g_chaos.fired("resident.loop").get("stall", 0) >= 1

    def test_expired_ticket_abandons_at_issue(self, rescoll):
        loop = get_resident_loop(rescoll)
        t = loop.submit([_compile_cached("apple", 0)], topk=16, lang=0,
                        deadline=Deadline.after(-1.0))
        with pytest.raises(DeadlineExceeded):
            t.wait(timeout=60)
        assert _count("deadline.abandoned.resident.issue") >= 1
        # an unbudgeted ticket right behind it is unaffected
        ((d, s, n),) = loop.submit([_compile_cached("apple", 0)],
                                   topk=16, lang=0).wait(timeout=60)
        assert n > 0


# ---------------------------------------------------------------------------
# rdb corruption: detected, quarantined, never served
# ---------------------------------------------------------------------------

class TestRdbChaos:
    def test_flipped_byte_trips_scrub_quarantine(self, tmp_path):
        coll = Collection("chaosrdb", tmp_path)
        coll.conf.pqr_enabled = False
        for i in range(20):
            docproc.index_document(coll, f"http://r.test/p{i}", _doc(i))
        assert coll.posdb.dump() is not None  # an on-disk run to maim
        g_chaos.enable(31, rate=0.0)
        target = g_chaos.corrupt_one_run(coll.posdb)
        assert target is not None
        assert _count("chaos.rdb.corrupted") == 1
        quarantined = coll.posdb.scrub()
        assert quarantined  # CRC verify tripped — the bytes never serve
        assert _count("rdb.corrupt_quarantined") >= 1
        g_chaos.disable()
        # the engine still answers from the surviving state
        res = search_device_batch(coll, ["cluster"], topk=5)
        assert res is not None

    def test_rdb_read_seam_fires_via_decide(self, tmp_path):
        coll = Collection("chaosrdb2", tmp_path)
        coll.conf.pqr_enabled = False
        for i in range(20):
            docproc.index_document(coll, f"http://r2.test/p{i}", _doc(i))
        coll.posdb.dump()
        g_chaos.enable(37, rate=0.0)
        g_chaos.configure("rdb.read", rate=1.0, kinds=("flipbyte",))
        from open_source_search_engine_tpu.index import posdb
        tid = ghash.term_id("cluster")
        coll.posdb.get_list(posdb.start_key(tid), posdb.end_key(tid))
        assert g_chaos.fired("rdb.read").get("flipbyte", 0) >= 1
        assert coll.posdb.scrub()  # the seam corrupted a real run


# ---------------------------------------------------------------------------
# membudget forced pressure
# ---------------------------------------------------------------------------

class TestMemBudgetChaos:
    def test_forced_pressure_runs_shed_pass(self):
        budget = MemBudget(limit=1 << 20)
        calls = []

        def handler(need):
            calls.append(need)
            return 0

        budget.add_pressure_handler(handler)
        g_chaos.enable(41, rate=0.0)
        g_chaos.configure("membudget.reserve", rate=1.0,
                          kinds=("pressure",))
        # the reservation FITS — chaos still forces the shed pass, so
        # the shed-before-refuse path gets exercised under load
        assert budget.reserve("chaostest", 1024) is True
        assert calls and calls[0] == 1024
        assert g_chaos.fired("membudget.reserve").get("pressure",
                                                      0) >= 1
        budget.release("chaostest", 1024)
        # unarmed, the same reservation never touches the handlers
        g_chaos.disable()
        calls.clear()
        assert budget.reserve("chaostest", 1024) is True
        assert not calls
        budget.release("chaostest", 1024)


# ---------------------------------------------------------------------------
# the soak gate (slow): crawl → index → serve under chaos
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_gate(tmp_path):
    from tests import soak_scenario
    rep = soak_scenario.run(tmp_path, n_pages=24, n_queries=48)
    assert rep["ok"], rep
    assert rep["lost_queries"] == 0
    assert rep["counters"]["deadline.abandoned"] > 0
    assert rep["counters"]["rdb.corrupt_quarantined"] > 0
