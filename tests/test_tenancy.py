"""Tenant plane — the ResidencyManager's LRU hot set, single-flight
cold starts, membudget pressure ordering, the admission gate's
weighted-fair tenant quotas, the delColl lifecycle, and the acceptance
criterion: a cold→hot promoted tenant answers identically to an
always-resident one.

The contract under test (serve/tenancy.py + serve/admission.py +
the engine/crawlbot wiring):

* residency is LRU-with-pinning, sized by ``max_resident`` and the
  membudget "device" label cap; parking stops the loop and zeroes the
  gauge but keeps the devcache base, so re-promotion is cheap AND
  bit-identical;
* a cold tenant's build is single-flight — riders join the leader's
  flight and shed under their own deadline instead of queueing blind;
* device pressure parks cold tenants (priority 10) BEFORE the cache
  plane flushes (priority 100) — one rung below shed-before-refuse;
* per-tenant admission quotas only bite on the QUEUE path (an idle
  gate lets any tenant borrow), and a shed for tenant A must never
  shed tenant B;
* crawlbot delete unserves before it purges: loop stopped, gauges
  zeroed, registry dropped — a deleted corpus neither answers from
  HBM nor keeps billing the budget.
"""

import random
import threading
import types
from collections import OrderedDict

import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import (Collection,
                                                            CollectionDb)
from open_source_search_engine_tpu.query import engine
from open_source_search_engine_tpu.query.engine import search_device_batch
from open_source_search_engine_tpu.serve import tenancy as tenancy_mod
from open_source_search_engine_tpu.serve.admission import (AdmissionGate,
                                                           Shed)
from open_source_search_engine_tpu.serve.crawlbot import CrawlBot, CrawlJob
from open_source_search_engine_tpu.serve.server import SearchHTTPServer
from open_source_search_engine_tpu.serve.tenancy import (ResidencyManager,
                                                         g_residency)
from open_source_search_engine_tpu.utils import deadline as deadline_mod
from open_source_search_engine_tpu.utils.membudget import g_membudget
from open_source_search_engine_tpu.utils.stats import g_stats

from .polling import wait_until

DOC = ("<html><head><title>{t}</title></head><body>"
       "<p>walrus {t} herd gathers on the {t} shore. "
       "The walrus colony of {t} dives deep.</p></body></html>")

QUERIES = ["walrus", "herd", "walrus shore", "colony", "nothinghere"]


def _mk_coll(tmp_path, name: str) -> Collection:
    c = Collection(name, tmp_path)
    c.conf.pqr_enabled = False
    docproc.index_document(c, f"http://{name}.test/p",
                           DOC.format(t=name))
    return c


@pytest.fixture(autouse=True)
def _plane_reset():
    """Tenancy tests mutate the process-wide singletons; leave them
    the way a fresh server boot expects them."""
    g_stats.reset()
    g_residency.reset()
    yield
    g_residency.reset()
    g_membudget.set_label_cap("device", 0)


def _count(name: str) -> int:
    return g_stats.snapshot()["counters"].get(name, 0)


def _key(r):
    return (-round(r.score, 3), r.docid)


# ---------------------------------------------------------------------------
# LRU hot set
# ---------------------------------------------------------------------------

class TestLru:
    def test_count_bound_evicts_least_recent(self, tmp_path):
        rm = ResidencyManager(max_resident=2)
        ca, cb, cc = (_mk_coll(tmp_path, n) for n in ("ta", "tb", "tc"))
        rm.loop_for(ca)
        rm.loop_for(cb)
        assert rm.resident_names() == ["ta", "tb"]
        rm.loop_for(cc)  # ta is LRU → parked
        assert rm.resident_names() == ["tb", "tc"]
        snap = rm.snapshot()
        assert snap["tenants"]["ta"]["resident"] is False
        assert snap["parked"] == 1
        # parking released the device gauge and stopped the loop
        assert g_membudget.used("device") == sum(
            t["device_bytes"] for t in snap["tenants"].values())
        assert ca._device_index is None
        rm.stop_all()

    def test_pin_protects_and_touch_refreshes_recency(self, tmp_path):
        rm = ResidencyManager(max_resident=2)
        ca, cb, cc = (_mk_coll(tmp_path, n) for n in ("pa", "pb", "pc"))
        rm.loop_for(ca)
        rm.loop_for(cb)
        rm.pin("pa")
        rm.loop_for(cc)  # pa pinned → pb (LRU unpinned) parks instead
        assert rm.resident_names() == ["pa", "pc"]
        # a fast-path hit must refresh recency: touch pc, promote pb —
        # with pa pinned and pc freshly touched there is no victim
        # besides pc, and the spare rule picks the LRU one
        loop_c = rm.loop_for(cc)
        assert rm.loop_for(cc) is loop_c  # fast path, same loop
        assert _count("tenancy.hit") >= 1
        rm.unpin("pa")
        rm.loop_for(cb)  # pa now LRU and unpinned → parked
        assert rm.resident_names() == ["pb", "pc"]
        rm.stop_all()

    def test_same_name_different_collection_never_aliases(self,
                                                          tmp_path):
        """A record is keyed by NAME but owned by a Collection OBJECT:
        a same-named collection from another registry (or a deleted-
        and-recreated one that skipped release()) must get its own
        loop, not the stale tenant's — serving the old object's device
        base would answer with the wrong corpus."""
        rm = ResidencyManager()
        old = _mk_coll(tmp_path / "old", "dup")
        loop_old = rm.loop_for(old)
        new = Collection("dup", tmp_path / "new")
        new.conf.pqr_enabled = False
        docproc.index_document(new, "http://dup.test/q",
                               DOC.format(t="fresh"))
        loop_new = rm.loop_for(new)
        assert loop_new is not loop_old
        assert _count("tenancy.stale_record") == 1
        # the stale record was fully released: the old object lost its
        # loop and device base, the record now bills the new object
        assert old._resident_loop is None
        assert old._device_index is None
        assert new._resident_loop is loop_new
        assert rm.snapshot()["tenants"]["dup"]["cold_starts"] == 1
        assert rm.loop_for(new) is loop_new  # fast path, new owner
        rm.stop_all()

    def test_repromotion_after_park_counts_a_cold_start(self, tmp_path):
        rm = ResidencyManager()
        ca = _mk_coll(tmp_path, "rp")
        rm.loop_for(ca)
        assert rm.snapshot()["tenants"]["rp"]["cold_starts"] == 1
        rm.park("rp")
        assert rm.snapshot()["tenants"]["rp"]["resident"] is False
        rm.loop_for(ca)
        snap = rm.snapshot()["tenants"]["rp"]
        assert snap["resident"] is True and snap["cold_starts"] == 2
        assert len(rm.coldstart_ms) == 2
        rm.stop_all()


# ---------------------------------------------------------------------------
# single-flight cold start
# ---------------------------------------------------------------------------

class TestSingleFlight:
    def test_concurrent_cold_queries_build_once(self, tmp_path,
                                                monkeypatch):
        rm = ResidencyManager()
        coll = _mk_coll(tmp_path, "sf")
        builds = []
        real = engine.get_device_index

        def counting(c):
            builds.append(c.name)
            return real(c)

        monkeypatch.setattr(engine, "get_device_index", counting)
        loops, errors = [], []
        start = threading.Barrier(8)

        def worker():
            try:
                start.wait(timeout=30)
                loops.append(rm.loop_for(coll))
            except BaseException as exc:  # noqa: BLE001
                errors.append(exc)

        ts = [threading.Thread(target=worker, daemon=True)
              for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not errors, errors
        assert builds == ["sf"]  # ONE build for 8 concurrent queries
        assert len(set(map(id, loops))) == 1
        assert rm.snapshot()["tenants"]["sf"]["cold_starts"] == 1
        rm.stop_all()

    def test_expired_rider_sheds_instead_of_waiting(self):
        """A rider whose deadline burned sheds (DeadlineExceeded → the
        serve edge's stale-or-504 ladder) rather than queueing blind
        behind a build it can no longer use."""
        rm = ResidencyManager()
        # a leader's flight is in progress (never completes here)
        rm._flights["rx"] = tenancy_mod._Flight()
        coll = types.SimpleNamespace(name="rx")
        base = _count("tenancy.rider_shed")
        with pytest.raises(deadline_mod.DeadlineExceeded):
            rm.loop_for(coll, deadline=deadline_mod.Deadline.after(0.0))
        assert _count("tenancy.rider_shed") == base + 1
        assert _count("tenancy.singleflight_join") >= 1

    def test_leader_failure_propagates_then_clears(self, tmp_path,
                                                   monkeypatch):
        rm = ResidencyManager()
        coll = _mk_coll(tmp_path, "lf")

        def boom(c):
            raise RuntimeError("build failed")

        monkeypatch.setattr(engine, "get_device_index", boom)
        with pytest.raises(RuntimeError, match="build failed"):
            rm.loop_for(coll)
        assert rm._flights == {}  # the failed flight is not wedged
        monkeypatch.undo()
        assert rm.loop_for(coll).alive  # next query promotes cleanly
        rm.stop_all()


# ---------------------------------------------------------------------------
# membudget pressure ordering
# ---------------------------------------------------------------------------

class TestPressure:
    def test_device_pressure_parks_cold_tenant_before_cache_plane(
            self, tmp_path):
        """The ladder's new rung: a device-label cap breach parks the
        LRU tenant (priority 10) and never reaches the higher-priority
        handlers — a parked tenant costs one transfer-speed cold
        start; a flushed cache costs every hot SERP."""
        rm = ResidencyManager()
        rm.attach(g_membudget)
        ca, cb = _mk_coll(tmp_path, "va"), _mk_coll(tmp_path, "vb")
        rm.loop_for(ca)
        rm.loop_for(cb)
        used = g_membudget.used("device")
        assert used > 0
        high_prio_calls = []
        g_membudget.add_pressure_handler(
            lambda need: high_prio_calls.append(need) or 0,
            priority=100, key="t.cacheish")
        try:
            g_membudget.set_label_cap("device", used)
            # one byte over the cap: relief must come from the
            # residency handler parking the LRU tenant (va — vb is the
            # hottest and gets spared)
            assert g_membudget.reserve("device", 1)
            g_membudget.release("device", 1)
        finally:
            g_membudget.set_label_cap("device", 0)
        assert rm.resident_names() == ["vb"]
        assert _count("tenancy.pressure_evict") == 1
        assert not high_prio_calls  # the ladder stopped one rung down
        rm.stop_all()


# ---------------------------------------------------------------------------
# weighted-fair tenant quotas (admission plane)
# ---------------------------------------------------------------------------

class TestQuotas:
    def test_idle_gate_lets_any_tenant_borrow(self):
        """Quota only bites on the queue path: with free inflight
        slots a lone tenant takes everything (work-conserving)."""
        gate = AdmissionGate(max_inflight=2, max_queue=2)
        with gate.admit("interactive", tenant="solo"):
            with gate.admit("interactive", tenant="solo"):
                pass
        t = gate.snapshot()["tenants"]["solo"]
        assert t["served"] == 2 and t["shed"] == 0

    def test_over_share_tenant_sheds_quota_quiet_tenant_queues(self):
        gate = AdmissionGate(max_inflight=1, max_queue=4)
        holder = gate.admit("interactive", tenant="quiet")
        release = threading.Event()
        results = []

        def queued_worker(tenant):
            try:
                dl = deadline_mod.Deadline.after(30.0)
                with gate.admit("interactive", deadline=dl,
                                tenant=tenant):
                    results.append(("served", tenant))
            except Shed as s:
                results.append((s.reason, tenant))

        # greedy's share with two active tenants: 4 * 1/2 = 2 waiters
        ts = [threading.Thread(target=queued_worker, args=("greedy",),
                               daemon=True) for _ in range(2)]
        for t in ts:
            t.start()
        wait_until(lambda: gate.snapshot()["tenants"].get(
            "greedy", {}).get("queued") == 2, desc="greedy queued")
        # the third greedy waiter is over-share → quota shed, synchronously
        with pytest.raises(Shed) as e:
            gate.admit("interactive",
                       deadline=deadline_mod.Deadline.after(30.0),
                       tenant="greedy")
        assert e.value.reason == "quota"
        # quiet still queues fine — greedy's overload never sheds it
        tq = threading.Thread(target=queued_worker, args=("quiet",),
                              daemon=True)
        tq.start()
        wait_until(lambda: gate.snapshot()["tenants"]["quiet"]
                   .get("queued") == 1, desc="quiet queued")
        holder.__exit__(None, None, None)
        release.set()
        for t in ts + [tq]:
            t.join(timeout=30)
        snap = gate.snapshot()["tenants"]
        assert snap["greedy"]["shed"] == 1
        assert snap["quiet"]["shed"] == 0
        assert ("served", "quiet") in results
        assert results.count(("served", "greedy")) == 2
        c = g_stats.snapshot()["counters"]
        assert c.get("admission.tenant.greedy.shed", 0) == 1
        assert c.get("admission.shed.reason.quota", 0) == 1

    def test_queue_full_displaces_over_share_victim(self):
        """A full queue with an over-share hog: the under-share
        arrival displaces the hog's newest waiter (shed ``quota``)
        instead of being refused ``queue_full``."""
        gate = AdmissionGate(max_inflight=1, max_queue=2)
        holder = gate.admit("interactive")  # legacy holder, no tenant
        results = []

        def queued_worker(tenant):
            try:
                dl = deadline_mod.Deadline.after(30.0)
                with gate.admit("interactive", deadline=dl,
                                tenant=tenant):
                    results.append(("served", tenant))
            except Shed as s:
                results.append((s.reason, tenant))

        # greedy fills the whole queue while it is the LONE active
        # tenant (share = unbounded: nobody else wants the capacity)
        ts = [threading.Thread(target=queued_worker, args=("greedy",),
                               daemon=True) for _ in range(2)]
        for t in ts:
            t.start()
        wait_until(lambda: gate.snapshot()["tenants"].get(
            "greedy", {}).get("queued") == 2, desc="queue full")
        # quiet arrives: queue is full, but greedy now holds 2 > its
        # share of 1 — the newest greedy waiter is displaced
        tq = threading.Thread(target=queued_worker, args=("quiet",),
                              daemon=True)
        tq.start()
        wait_until(lambda: ("quota", "greedy") in results,
                   desc="greedy waiter displaced")
        holder.__exit__(None, None, None)
        for t in ts + [tq]:
            t.join(timeout=30)
        assert ("served", "quiet") in results
        assert results.count(("served", "greedy")) == 1
        assert gate.snapshot()["tenants"]["quiet"]["shed"] == 0

    def test_weights_skew_the_grant_order(self):
        """Within a tier the grant goes to the waiter whose tenant has
        the lowest inflight/weight — a weight-3 tenant drains 3× the
        work of a weight-1 tenant under contention."""
        gate = AdmissionGate(max_inflight=1, max_queue=8)
        gate.set_tenant_weight("gold", 3.0)
        holder = gate.admit("interactive", tenant="gold")
        order = []
        lock = threading.Lock()

        def queued_worker(tenant):
            dl = deadline_mod.Deadline.after(30.0)
            with gate.admit("interactive", deadline=dl, tenant=tenant):
                with lock:
                    order.append(tenant)

        # queue one bronze FIRST, then one gold: FIFO would serve
        # bronze; weighted-fair must pick gold (holder's release zeroes
        # gold's inflight → gold load 0/3 < bronze 0/1 ties → FIFO
        # breaks the tie, so make bronze carry inflight instead)
        tb = threading.Thread(target=queued_worker, args=("bronze",),
                              daemon=True)
        tb.start()
        wait_until(lambda: gate.snapshot()["tenants"].get(
            "bronze", {}).get("queued") == 1, desc="bronze queued")
        tg = threading.Thread(target=queued_worker, args=("gold",),
                              daemon=True)
        tg.start()
        wait_until(lambda: gate.snapshot()["tenants"].get(
            "gold", {}).get("queued") == 1, desc="gold queued")
        # gold already has 1 inflight (the holder): load 1/3 = 0.33 vs
        # bronze 0/1 = 0.0 → bronze first — the weight can't starve a
        # zero-load tenant. Release and check both finish.
        holder.__exit__(None, None, None)
        tb.join(timeout=30)
        tg.join(timeout=30)
        assert order[0] == "bronze"  # lowest load/weight wins the slot
        assert set(order) == {"bronze", "gold"}

    def test_legacy_no_tenant_requests_are_untouched(self):
        """tenant=None rides the exact pre-tenant FIFO path — no
        ledger entries, no quota sheds."""
        gate = AdmissionGate(max_inflight=1, max_queue=1)
        with gate.admit("interactive"):
            pass
        assert gate.snapshot()["tenants"] == {}


# ---------------------------------------------------------------------------
# delete lifecycle (the delColl fix)
# ---------------------------------------------------------------------------

class TestDeleteLifecycle:
    def test_crawlbot_delete_unserves_and_unbills(self, tmp_path):
        """Regression: crawlbot delete used to rmtree the directory
        while the Collection object (and its resident loop + memtable
        gauges) stayed registered — the corpus kept answering from HBM
        and billing the budget forever."""
        colldb = CollectionDb(tmp_path)
        bot = CrawlBot(colldb)
        mem_before = g_membudget.used("memtable")
        coll = colldb.get("crawl_wipe")
        coll.conf.pqr_enabled = False
        docproc.index_document(coll, "http://wipe.test/p",
                               DOC.format(t="wipe"))
        assert g_membudget.used("memtable") > mem_before
        loop = engine.get_resident_loop(coll)  # serves via g_residency
        assert loop.alive
        assert g_membudget.used("device") > 0
        # a job record without a live crawl thread: delete() only
        # needs the registry entry
        bot.jobs["wipe"] = CrawlJob(name="wipe", loop=None, max_pages=1)
        assert bot.delete("wipe")
        assert not loop.alive  # resident loop stopped
        assert "crawl_wipe" not in colldb.colls  # registry dropped
        assert "crawl_wipe" not in g_residency.snapshot()["tenants"]
        assert g_membudget.used("device") == 0
        assert g_membudget.used("memtable") <= mem_before
        assert not (tmp_path / "coll" / "crawl_wipe").exists()
        # a recreated collection of the same name starts empty
        fresh = colldb.get("crawl_wipe")
        assert fresh.num_docs == 0


# ---------------------------------------------------------------------------
# /admin/tenants
# ---------------------------------------------------------------------------

class TestAdminPage:
    def test_page_joins_residency_and_admission_ledgers(self, tmp_path):
        srv = SearchHTTPServer(tmp_path, port=0)
        try:
            coll = srv.colldb.get("main")
            coll.conf.pqr_enabled = False
            docproc.index_document(coll, "http://adm.test/p",
                                   DOC.format(t="admin"))
            st, body, ct = srv.handle("GET", "/search",
                                      {"q": "walrus"}, b"")
            assert st == 200
            st, body, ct = srv.handle("GET", "/admin/tenants",
                                      {"format": "json"}, b"")
            assert st == 200 and ct == "application/json"
            import json as json_mod
            snap = json_mod.loads(body)
            # the default-collection tenant shows up in BOTH ledgers
            assert snap["residency"]["tenants"]["main"]["resident"]
            assert snap["admission"]["main"]["served"] >= 1
            st, body, ct = srv.handle("GET", "/admin/tenants", {}, b"")
            assert st == 200 and ct == "text/html"
            assert "RESIDENT" in body and "main" in body
            # per-tenant counters reach /metrics with outcome labels
            st, body, ct = srv.handle("GET", "/metrics", {}, b"")
            assert ('osse_tenant_requests_total{tenant="main",'
                    'outcome="served"}') in body
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# the front door over more tenants than slots
# ---------------------------------------------------------------------------

class TestZipfOverTheFrontDoor:
    def test_a_seeded_zipf_stream_is_answered_whole_and_rides_the_lru(
            self, tmp_path):
        """One server, 24 collections, 8 slots, 150 queries whose only
        random draw is the collection (Zipf 1.5, seeded): every arrival
        is answered 200, nothing is shed, the membudget refuses nothing
        (parking is the relief valve), the resident count stays inside
        the budget, and the hits and cold starts are exactly those of an
        LRU of 8 over the same stream."""
        n_colls, slots, n_q = 24, 8, 150
        srv = SearchHTTPServer(tmp_path, port=0)
        try:
            names = [f"t{i:02d}" for i in range(n_colls)]
            for name in names:
                coll = srv.colldb.get(name)
                # cache off: every request must reach the engine
                coll.conf.result_cache_ttl = 0
                coll.conf.pqr_enabled = False
                docproc.index_document(coll, f"http://{name}.test/p",
                                       DOC.format(t=name))
            g_residency.configure(max_resident=slots)
            g_stats.reset()
            rng = random.Random(23)
            weights = [1.0 / (r + 1) ** 1.5 for r in range(n_colls)]
            lru: OrderedDict = OrderedDict()
            hits = colds = 0
            for qi in range(n_q):
                c = rng.choices(range(n_colls), weights=weights, k=1)[0]
                code, _, _ = srv.handle(
                    "GET", "/search",
                    {"q": QUERIES[qi % 4], "c": names[c]}, b"")
                assert code == 200, (qi, names[c])
                if c in lru:
                    hits += 1
                    lru.move_to_end(c)
                else:
                    colds += 1
                    lru[c] = True
                    if len(lru) > slots:
                        lru.popitem(last=False)
            assert colds > slots  # the tail did churn through the slots
            assert (_count("tenancy.hit"), _count("tenancy.coldstart")) \
                == (hits, colds)
            snap = g_residency.snapshot()
            assert snap["coldstarts"] == colds
            assert snap["resident"] == len(lru) == slots
            assert set(g_residency.resident_names()) == {
                names[c] for c in lru}
            counters = g_stats.snapshot()["counters"]
            assert not [k for k, v in counters.items() if v and k.startswith(
                ("membudget.reject.", "admission.shed."))], counters
        finally:
            srv.stop()
        assert g_residency.resident_names() == []


# ---------------------------------------------------------------------------
# acceptance: cold→hot parity
# ---------------------------------------------------------------------------

class TestColdHotParity:
    def test_repromoted_tenant_answers_identically(self, tmp_path):
        """The acceptance criterion: park a tenant, re-promote it via
        a query, and get results identical to the always-resident
        run (and to the one-shot reference) — the parked state must
        lose no index state."""
        coll = _mk_coll(tmp_path, "parity")
        for i in range(4):
            docproc.index_document(
                coll, f"http://parity.test/extra{i}",
                DOC.format(t=f"extra{i} walrus herd"))
        reference = search_device_batch(coll, QUERIES, topk=10,
                                        site_cluster=False)
        hot = search_device_batch(coll, QUERIES, topk=10,
                                  site_cluster=False, resident=True)
        assert g_residency.snapshot()["tenants"]["parity"]["resident"]
        g_residency.park("parity")
        assert coll._device_index is None
        assert not g_residency.snapshot()["tenants"]["parity"]["resident"]
        # the next resident query cold-starts from the parked state
        warm = search_device_batch(coll, QUERIES, topk=10,
                                   site_cluster=False, resident=True)
        assert g_residency.snapshot()["tenants"]["parity"]["cold_starts"] \
            == 2
        for q, a, b, c in zip(QUERIES, reference, hot, warm):
            assert b.total_matches == a.total_matches == c.total_matches, q
            assert sorted(map(_key, b.results)) \
                == sorted(map(_key, a.results)) \
                == sorted(map(_key, c.results)), q
