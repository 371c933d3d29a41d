"""Admission plane — bounded tiered gate, shed ladder, queue caps.

The contract under test (serve/admission.py + the serve/query wiring):

* tiers classify at the front door (param > header > niceness bit) and
  ride X-OSSE-Priority through scatter legs to the node planes;
* the gate admits by strict tier order (interactive first, FIFO within
  a tier) and sheds cheaply — queue_full / slo-degraded / membudget
  pressure / predicted-delay-eats-deadline — BEFORE work starts;
* the serve edge turns a shed into the cache plane's same-generation
  stale answer marked degraded, else 503 + Retry-After, every one
  counted;
* QueryBatcher and ResidentLoop queues are bounded (QueueFull, counted,
  gauged on the membudget "serve" label) — an overload burst cannot
  grow host memory without bound;
* a banned client hammering the endpoint can never re-extend its own
  ban (AutoBan robustness), and overload composed with a chaos-wedged
  twin still hedges, bounds interactive latency, and loses no request.
"""

import threading
import time

import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.parallel import cluster as cl
from open_source_search_engine_tpu.query.resident import ResidentLoop
from open_source_search_engine_tpu.serve import admission as admission_mod
from open_source_search_engine_tpu.serve.admission import (AdmissionGate,
                                                           Shed)
from open_source_search_engine_tpu.serve.server import (QueryBatcher,
                                                        SearchHTTPServer)
from open_source_search_engine_tpu.utils import priority as priority_mod
from open_source_search_engine_tpu.utils.chaos import g_chaos
from open_source_search_engine_tpu.utils.deadline import Deadline
from open_source_search_engine_tpu.utils.membudget import g_membudget
from open_source_search_engine_tpu.utils.priority import (QueueFull,
                                                          classify)
from open_source_search_engine_tpu.utils.stats import g_stats

from .polling import wait_until


@pytest.fixture(autouse=True)
def _stats_reset():
    g_chaos.disable()
    g_stats.reset()
    yield
    g_chaos.disable()


def _count(name: str) -> int:
    return g_stats.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# tier vocabulary
# ---------------------------------------------------------------------------

class TestClassify:
    def test_precedence_param_header_niceness(self):
        assert classify({"tier": "crawlbot"}) == "crawlbot"
        assert classify({}, header_tier="suggest") == "suggest"
        assert classify({"tier": "suggest"},
                        header_tier="crawlbot") == "suggest"
        assert classify({}, niceness=1) == "crawlbot"
        assert classify({}) == "interactive"

    def test_unknown_values_classify_up(self):
        # misclassifying UP is safer than starving a human
        assert classify({"tier": "root"}) == "interactive"
        assert priority_mod.tier_from_header("ADMIN") is None
        assert priority_mod.tier_from_header(" Crawlbot ") == "crawlbot"

    def test_tier_niceness_mapping(self):
        assert priority_mod.tier_niceness("interactive") == 0
        assert priority_mod.tier_niceness("crawlbot") == 1
        assert priority_mod.tier_niceness(None) == 0


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

class TestAdmissionGate:
    def test_admit_release_counts_and_histogram(self):
        g = AdmissionGate(max_inflight=2)
        with g.admit("interactive"):
            assert g.snapshot()["inflight"] == 1
        assert g.idle()
        assert _count("admission.admitted") == 1
        lat = g_stats.snapshot()["latencies"]
        assert lat["admission.queue_delay"]["count"] == 1

    def test_priority_wake_order(self):
        g = AdmissionGate(max_inflight=1, max_queue=8, max_wait_s=5.0)
        tok = g.admit("interactive")
        order = []

        def waiter(tier):
            with g.admit(tier):
                order.append(tier)

        # crawlbot queues FIRST, interactive second — the grant must
        # still go tier-order, not FIFO across tiers
        tc = threading.Thread(target=waiter, args=("crawlbot",))
        tc.start()
        wait_until(lambda: g.snapshot()["queued"]["crawlbot"] == 1,
                   desc="crawlbot queued")
        ti = threading.Thread(target=waiter, args=("interactive",))
        ti.start()
        wait_until(lambda: g.snapshot()["queued"]["interactive"] == 1,
                   desc="interactive queued")
        tok.__exit__(None, None, None)
        ti.join(5.0)
        tc.join(5.0)
        assert order == ["interactive", "crawlbot"]
        assert _count("admission.queued") == 2
        assert g.idle()

    def test_queue_full_sheds(self):
        g = AdmissionGate(max_inflight=1, max_queue=1, max_wait_s=5.0)
        tok = g.admit("interactive")
        t = threading.Thread(
            target=lambda: g.admit("interactive").__exit__(
                None, None, None))
        t.start()
        wait_until(lambda: g.snapshot()["queued_total"] == 1,
                   desc="one waiter queued")
        with pytest.raises(Shed) as ei:
            g.admit("interactive")
        assert ei.value.reason == "queue_full"
        assert _count("admission.queue_full") == 1
        tok.__exit__(None, None, None)
        t.join(5.0)

    def test_degraded_signal_sheds_background_not_interactive(self):
        g = AdmissionGate(degraded_fn=lambda: True)
        for tier in ("crawlbot", "suggest"):
            with pytest.raises(Shed) as ei:
                g.admit(tier)
            assert ei.value.reason == "signal"
        with g.admit("interactive"):
            pass
        assert g.shed_total == 2

    def test_membudget_pressure_sheds_background(self):
        g = AdmissionGate(pressure_fn=lambda: True)
        with pytest.raises(Shed):
            g.admit("crawlbot")
        with g.admit("interactive"):
            pass

    def test_predicted_delay_vs_deadline_sheds_at_door(self):
        g = AdmissionGate(max_inflight=1)
        g._svc_s = 1.0  # pessimistic EWMA: ~1s per admitted slot
        tok = g.admit("interactive")
        with pytest.raises(Shed) as ei:
            g.admit("interactive", deadline=Deadline.after(0.05))
        assert ei.value.reason == "deadline"
        assert ei.value.retry_after_s >= 1.0
        tok.__exit__(None, None, None)

    def test_wait_timeout_sheds_and_unqueues(self):
        g = AdmissionGate(max_inflight=1, max_wait_s=0.05)
        tok = g.admit("interactive")
        with pytest.raises(Shed) as ei:
            g.admit("interactive")
        assert ei.value.reason == "timeout"
        assert g.snapshot()["queued_total"] == 0  # waiter removed
        tok.__exit__(None, None, None)
        assert g.idle()


# ---------------------------------------------------------------------------
# bounded dispatch queues (satellite: unbounded today → capped)
# ---------------------------------------------------------------------------

class _FakeDI:
    """issue/collect stub: issue blocks on an event so tickets pile up
    in the queue (the overload shape the cap exists for)."""
    _built_version = 1

    def __init__(self, ev):
        self.ev = ev

    def issue_batch(self, plans, topk=0, lang=0):
        self.ev.wait(5.0)
        return list(plans)

    def collect_batch(self, pending):
        return [("d", "s", 0) for _ in pending]


class TestQueueCaps:
    def test_batcher_cap_raises_queuefull(self):
        b = QueryBatcher(lambda key, qs: ["r"] * len(qs))
        try:
            b.MAX_QUEUE = 0  # instance override: every enqueue refused
            with pytest.raises(QueueFull):
                b.search(("main", 10, 0), "words")
            assert _count("admission.queue_full") == 1
        finally:
            b.stop()

    def test_batcher_idle_flush_launches_immediately(self):
        b = QueryBatcher(lambda key, qs: ["r"] * len(qs))
        try:
            assert b.search(("main", 10, 0), "words") == "r"
            assert _count("admission.wave.idle_flush") >= 1
        finally:
            b.stop()

    def test_resident_cap_fails_ticket_and_gauges_membudget(self):
        ev = threading.Event()
        di = _FakeDI(ev)
        loop = ResidentLoop(lambda: di, lambda: 1, max_queue=2,
                            name="capped")
        try:
            t1 = loop.submit([b"p1"])  # loop blocks inside issue
            wait_until(lambda: loop.waves_issued == 0
                       and not loop._queue, timeout=2.0,
                       desc="first ticket taken for issue")
            t2 = loop.submit([b"p2"])
            t3 = loop.submit([b"p3"])
            # queue at cap → gauged on the membudget "serve" label
            lbl = g_membudget.snapshot()["labels"].get("serve", {})
            assert lbl.get("gauged", 0) > 0
            t4 = loop.submit([b"p4"])
            with pytest.raises(QueueFull):
                t4.wait(timeout=1.0)
            assert _count("admission.queue_full") == 1
            ev.set()
            for t in (t1, t2, t3):
                assert t.wait(timeout=5.0)
            assert _count("resident.idle_flush") >= 1
        finally:
            ev.set()
            loop.stop()


# ---------------------------------------------------------------------------
# serve-edge integration: classification, shed ladder, autoban
# ---------------------------------------------------------------------------

@pytest.fixture
def srv(tmp_path):
    s = SearchHTTPServer(str(tmp_path), port=0)
    coll = s.colldb.get("main")
    for i in range(4):
        docproc.index_document(
            coll, f"http://adm{i}.test/p{i}",
            f"<html><title>t{i}</title><body><p>admission corpus "
            f"words number{i}</p></body></html>")
    yield s
    s.stop()


def _search(s, niceness=0, **q):
    return s.handle("GET", "/search",
                    {k: str(v) for k, v in q.items()}, b"",
                    client_ip="9.9.9.9", niceness=niceness)


class TestServeEdge:
    def test_front_door_classification_counted(self, srv):
        assert _search(srv, q="admission corpus")[0] == 200
        assert _count("admission.tier.interactive") == 1
        assert _search(srv, q="admission corpus",
                       tier="crawlbot")[0] == 200
        assert _count("admission.tier.crawlbot") == 1
        # the niceness bit self-identifies background callers
        assert _search(srv, q="admission corpus", niceness=1)[0] == 200
        assert _count("admission.tier.crawlbot") == 2

    def test_shed_refuses_with_retry_after(self, srv):
        srv.admission = AdmissionGate(degraded_fn=lambda: True)
        code, body, ctype = _search(srv, q="never cached words",
                                    tier="crawlbot")
        assert code == 503
        assert '"retryAfter"' in body
        assert _count("admission.shed.refused") == 1
        # the Retry-After header rides the side channel for the HTTP
        # handler to emit
        hdrs = dict(admission_mod.pop_response_headers())
        assert "Retry-After" in hdrs
        # interactive still admitted under the same signal
        assert _search(srv, q="admission corpus")[0] == 200

    def test_shed_serves_same_generation_stale_first(self, srv):
        coll = srv.colldb.get("main")
        coll.conf.result_cache_ttl = 0.05
        srv.admission = AdmissionGate(degraded_fn=lambda: True)
        code, page, _ = _search(srv, q="admission corpus")
        assert code == 200  # interactive primed the result cache
        gen = srv._result_gen(coll)
        ckey = ("main", "admission corpus", 10, 0, "json")
        wait_until(
            lambda: not srv._result_cache.lookup(ckey, gen=gen)[0],
            timeout=2.0, desc="result cache entry expiry")
        # crawlbot sheds → the just-expired page beats a refusal
        code2, page2, _ = _search(srv, q="admission corpus",
                                  tier="crawlbot")
        assert code2 == 200 and page2 == page
        assert _count("admission.shed.stale") == 1
        assert srv.stats.get("admission_stale") == 1

    def test_fresh_cache_hit_bypasses_gate(self, srv):
        coll = srv.colldb.get("main")
        coll.conf.result_cache_ttl = 30.0
        code, page, _ = _search(srv, q="admission corpus")
        assert code == 200
        # now close the gate entirely: the hot head must keep answering
        srv.admission = AdmissionGate(max_inflight=0, max_queue=0)
        code2, page2, _ = _search(srv, q="admission corpus")
        assert code2 == 200 and page2 == page

    def test_autoban_cannot_self_extend(self, srv):
        """Satellite (a): a banned client hammering the endpoint must
        be re-admitted after BAN_COOLDOWN_S — rejected requests do NOT
        charge the rate window, so the ban cannot re-extend forever."""
        coll = srv.colldb.get("main")
        coll.conf.autoban_qps = 5
        srv.BAN_COOLDOWN_S = 0.3  # instance override: fast cooldown
        ip = "6.6.6.6"
        t0 = time.monotonic()
        first_429 = None
        readmitted_at = None
        # sustained offered load for ~3 cooldowns, no backoff at all
        while time.monotonic() - t0 < 1.0:
            code, _, _ = srv.handle("GET", "/search",
                                    {"q": "admission corpus"}, b"",
                                    client_ip=ip)
            now = time.monotonic()
            if code == 429 and first_429 is None:
                first_429 = now
            if (first_429 is not None and code == 200
                    and now > first_429 + srv.BAN_COOLDOWN_S):
                readmitted_at = now
                break
            time.sleep(0.002)
        assert first_429 is not None, "hammering never tripped autoban"
        assert readmitted_at is not None, \
            "ban never expired under sustained load (self-extension)"
        assert _count("autoban.rejected") > 0


# ---------------------------------------------------------------------------
# header propagation: the tier rides scatter legs to the node planes
# ---------------------------------------------------------------------------

def _doc(i: int) -> str:
    return (f"<html><title>d{i}</title><body><p>cluster shared words "
            f"number{i}</p></body></html>")


class TestTierPropagation:
    def test_node_honors_priority_header(self, tmp_path):
        node = cl.ShardNodeServer(tmp_path / "n0", port=0)
        node.start()
        conf = cl.HostsConf.parse(
            f"num-mirrors: 0\n127.0.0.1:{node.port}")
        client = cl.ClusterClient(conf, use_heartbeat=False)
        try:
            client.index_document("http://t.test/d0", _doc(0))
            with priority_mod.bind_tier("crawlbot"):
                res = client.search("cluster shared words", topk=5)
            assert res.total_matches > 0
            assert _count("admission.node.crawlbot") >= 1
        finally:
            client.close()
            node.stop()


# ---------------------------------------------------------------------------
# chaos-composed overload: wedge one twin WHILE offered > capacity
# ---------------------------------------------------------------------------

class TestChaosOverload:
    def test_wedged_twin_under_overload_hedges_and_sheds_counted(
            self, tmp_path):
        """Satellite (c): with one twin wedged and more offered work
        than the gate admits, hedges still fire, interactive stays
        bounded, and every shed is accounted for — nothing lost."""
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
        srv = SearchHTTPServer(str(tmp_path / "front"), cluster=client)
        srv.admission = AdmissionGate(max_inflight=2, max_queue=4,
                                      max_wait_s=2.0)
        lock = threading.Lock()
        codes: dict[int, int] = {}
        try:
            for i in range(12):
                client.index_document(f"http://t.test/d{i}", _doc(i))
            g_chaos.enable(17, rate=0.0)
            g_chaos.configure("cluster.node", rate=1.0,
                              kinds=("wedge",),
                              match=str(nodes[0].port), delay_s=0.05)

            def one(k: int) -> None:
                tier = "crawlbot" if k % 3 == 0 else "interactive"
                try:
                    code, _, _ = srv.handle(
                        "GET", "/search",
                        {"q": f"cluster shared number{k % 12}",
                         "tier": tier, "deadline_ms": "800"},
                        b"", client_ip="7.7.7.7")
                except Exception:  # noqa: BLE001 — a lost reply IS the bug
                    code = -1
                with lock:
                    codes[code] = codes.get(code, 0) + 1

            n_req = 36
            threads = [threading.Thread(target=one, args=(k,))
                       for k in range(n_req)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
            # nothing silently lost: every offered request came back,
            # and the refused ones match the shed counter exactly
            assert sum(codes.values()) == n_req
            assert codes.get(-1, 0) == 0
            refused = codes.get(503, 0)
            assert refused + codes.get(504, 0) > 0  # it DID overload
            assert refused == _count("admission.shed.refused")
            # the wedged twin did not disable hedging
            assert g_chaos.fired("cluster.node").get("wedge", 0) >= 1
            assert _count("transport.hedge_fired") >= 1
            # interactive latency stayed bounded (deadline + gate cap,
            # not the wedge's seconds-long stall)
            lat = g_stats.snapshot()["latencies"].get(
                "serve.search.interactive")
            assert lat is not None and lat["count"] > 0
            assert lat["p99_ms"] < 3000.0
            # the gate shed BEFORE the membudget had to refuse
            assert _count("membudget.reject.serve") == 0
            # the gate drained: no leaked slots, no metastable queue
            wait_until(srv.admission.idle, timeout=5.0,
                       desc="admission gate drained")
            # and recovered: the next request, of the tier that was
            # shed, is admitted and answered (the twin is still wedged)
            code, _, _ = srv.handle(
                "GET", "/search",
                {"q": "cluster shared number3", "tier": "crawlbot"},
                b"", client_ip="7.7.7.8")
            assert code == 200
        finally:
            g_chaos.disable()
            srv.stop()
            client.close()
            for n in nodes:
                n.stop()
