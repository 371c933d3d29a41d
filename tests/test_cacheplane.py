"""Cache plane tests — generation invalidation, single-flight,
stale-while-revalidate, memory-pressure shedding, and the cluster
wiring (RdbCache consolidation).

Pins the contract of :mod:`..cache.plane` plus the two hot-path
integrations: a write on shard 1 must never flush shard 0's leg
entries (per-shard generations), and the inject→query→delete→query
round trip must never serve a stale SERP — the write bumps the
generation BEFORE the RPC leaves, and the bump is observed
cluster-wide through the X-OSSE-Gen reply headers.
"""

import json
import threading
import time
import urllib.request

import pytest

from open_source_search_engine_tpu.cache import GenCache, g_cacheplane
from open_source_search_engine_tpu.parallel import cluster as cl
from open_source_search_engine_tpu.serve.server import SearchHTTPServer
from open_source_search_engine_tpu.utils import ghash
from open_source_search_engine_tpu.utils.membudget import g_membudget
from open_source_search_engine_tpu.utils.parms import CollectionConf


def _doc(i, words="cluster shared words"):
    return (f"<html><head><title>Doc {i}</title></head><body>"
            f"<p>{words} token{i}.</p></body></html>")


def _node(tmp_path, name, n_docs=3, start=True, port=0):
    node = cl.ShardNodeServer(tmp_path / name, port=port)
    for i in range(n_docs):
        node.handle("/rpc/index", {"url": f"http://t.test/{name}{i}",
                                   "content": _doc(i)})
    if start:
        node.start()
    return node


def _drain(client, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while client.pending_writes and time.monotonic() < deadline:
        time.sleep(0.02)
    assert client.pending_writes == 0


def _url_for_shard(client, shard, tag):
    """A url that docid-routes to the given shard (probe, like the
    reference's test fixtures pick per-group urls)."""
    for i in range(1000):
        u = f"http://gen.test/{tag}{i}"
        if int(client.hostmap.shard_of_docid(ghash.doc_id(u))) == shard:
            return u
    raise AssertionError("no url routed to shard %d" % shard)


# ---------------------------------------------------------------------------
# GenCache core contract
# ---------------------------------------------------------------------------

class TestGenCache:
    def test_generation_invalidation_is_o1(self):
        c = GenCache("t.gen", ttl_s=60)
        c.put("k", "old", gen=1)
        assert c.lookup("k", gen=1) == (True, "old")
        # the generation moving kills the entry with zero scanning
        assert c.lookup("k", gen=2) == (False, None)
        c.put("k", "new", gen=2)
        assert c.lookup("k", gen=2) == (True, "new")

    def test_a_cache_collected_under_the_budgets_lock_is_no_deadlock(self):
        """The collector runs finalizers wherever an allocation lands,
        also on a thread that holds the budget's lock (it did, inside
        ``set_gauge``, and the suite hung there): the finalizer takes
        no lock, and the next locked reader drops the gauge."""
        c = GenCache("t.finalized", ttl_s=60)
        c.put("k", "v" * 4096)
        assert g_membudget.used("cache") > 0
        before = g_membudget.used("cache")
        done = threading.Event()

        def collect_under_the_lock(cache):
            with g_membudget._lock:
                cache.__del__()     # what the collector would run here
            done.set()

        threading.Thread(target=collect_under_the_lock, args=(c,),
                         daemon=True).start()
        assert done.wait(10.0), "the finalizer waited for its own lock"
        assert g_membudget.used("cache") < before

    def test_gen_fn_supplies_default_generation(self):
        gen = [1]
        c = GenCache("t.genfn", ttl_s=60, gen_fn=lambda: gen[0])
        c.put("k", "v")
        assert c.get("k") == "v"
        gen[0] = 2
        assert c.get("k") is None

    def test_none_values_cacheable(self):
        # negative DNS answers ARE the cached value — lookup's (hit,
        # value) form must distinguish them from a miss
        c = GenCache("t.none", ttl_s=60)
        c.put("k", None)
        assert c.lookup("k") == (True, None)
        assert c.lookup("absent") == (False, None)

    def test_eviction_drops_dead_generation_first(self):
        c = GenCache("t.evict", ttl_s=60, max_entries=4)
        for i in range(3):
            c.put(("dead", i), i, gen=1)
        c.put(("live", 0), 0, gen=2)
        # at cap: the room-making sweep must shed the dead-gen entries
        # and keep the one live entry
        c.put(("live", 1), 1, gen=2)
        assert c.lookup(("live", 0), gen=2) == (True, 0)
        assert c.lookup(("live", 1), gen=2) == (True, 1)
        assert all(("dead", i) not in c._d for i in range(3))

    def test_single_flight_one_compute(self):
        c = GenCache("t.sf", ttl_s=60)
        n_threads = 8
        calls = []
        barrier = threading.Barrier(n_threads)
        statuses = []
        lock = threading.Lock()

        def compute():
            calls.append(1)
            time.sleep(0.25)  # hold the flight open while others join
            return "answer"

        def worker():
            barrier.wait()
            v, status = c.get_or_compute("hot", compute)
            with lock:
                statuses.append((v, status))

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert len(calls) == 1  # the whole stampede ran ONE compute
        assert all(v == "answer" for v, _ in statuses)
        kinds = [s for _, s in statuses]
        assert kinds.count("miss") == 1
        assert set(kinds) <= {"miss", "join", "hit"}

    def test_single_flight_leader_error_propagates(self):
        c = GenCache("t.sferr", ttl_s=60)
        entered = threading.Event()
        errors = []

        def compute():
            entered.set()
            time.sleep(0.1)
            raise RuntimeError("boom")

        def leader():
            try:
                c.get_or_compute("k", compute)
            except RuntimeError as e:
                errors.append(("leader", str(e)))

        def follower():
            entered.wait(5)
            try:
                c.get_or_compute("k", compute)
            except RuntimeError as e:
                errors.append(("follower", str(e)))

        tl = threading.Thread(target=leader)
        tf = threading.Thread(target=follower)
        tl.start()
        tf.start()
        tl.join(timeout=10)
        tf.join(timeout=10)
        # retrying in lockstep is the stampede single-flight prevents:
        # the leader's failure reaches every waiter, and at most one
        # late-arriving follower re-runs the compute
        assert ("leader", "boom") in errors
        assert len(errors) == 2

    def test_compute_racing_a_write_stores_a_dead_entry(self):
        # the generation is captured at ENTRY: a write landing during
        # the compute must leave the stored entry dead (a later miss),
        # never stamp the pre-write result with the post-write gen
        gen = [1]
        c = GenCache("t.race", ttl_s=60, gen_fn=lambda: gen[0])

        def compute():
            gen[0] = 2  # a write lands mid-compute
            return "pre-write"

        v, status = c.get_or_compute("k", compute)
        assert (v, status) == ("pre-write", "miss")
        # the entry carries the entry-time gen (1) → post-write lookups
        # (gen 2) miss instead of serving the pre-write value as fresh
        assert c.lookup("k") == (False, None)

    def test_no_join_across_a_generation_move(self):
        # a flight started under gen 1 must not hand its (pre-write)
        # result to a caller arriving after the write moved gen to 2
        gen = [1]
        c = GenCache("t.sfgen", ttl_s=60, gen_fn=lambda: gen[0])
        entered = threading.Event()
        release = threading.Event()
        out = {}

        def slow_pre_write():
            entered.set()
            release.wait(5)
            return "pre-write"

        t = threading.Thread(target=lambda: out.update(
            leader=c.get_or_compute("k", slow_pre_write)))
        t.start()
        assert entered.wait(5)
        gen[0] = 2  # the write lands while the leader computes
        v, status = c.get_or_compute("k", lambda: "post-write")
        assert (v, status) == ("post-write", "miss")  # NOT a join
        release.set()
        t.join(timeout=10)
        assert out["leader"] == ("pre-write", "miss")
        # the leader's late put is stamped gen 1 → dead at gen 2
        assert c.lookup("k") == (False, None)

    def test_swr_serves_stale_then_refreshes(self):
        c = GenCache("t.swr", ttl_s=0.05)
        versions = iter(["v1", "v2"])
        v, status = c.get_or_compute("k", lambda: next(versions))
        assert (v, status) == ("v1", "miss")
        time.sleep(0.08)  # past TTL, inside the swr window
        v, status = c.get_or_compute("k", lambda: next(versions),
                                     swr_s=10.0)
        assert (v, status) == ("v1", "stale")  # served immediately
        # the background refresh lands the fresh value under a new TTL
        for _ in range(100):
            if c.get("k") == "v2":
                break
            time.sleep(0.02)
        assert c.get("k") == "v2"
        assert c.stats()["stale_served"] == 1

    def test_swr_never_crosses_a_generation_move(self):
        c = GenCache("t.swrgen", ttl_s=0.05)
        c.put("k", "old", gen=1)
        time.sleep(0.08)
        # expired AND the generation moved: swr must NOT soften a
        # write — this is a plain miss
        v, status = c.get_or_compute("k", lambda: "new", gen=2,
                                     swr_s=10.0)
        assert (v, status) == ("new", "miss")

    def test_swr_refresh_racing_a_write_stores_a_dead_entry(self):
        # the background SWR refresh stamps with the gen the stale
        # serve happened under — a write landing mid-refresh must
        # leave a dead entry, not a pre-write value passing as fresh
        gen = [1]
        c = GenCache("t.swrrace", ttl_s=0.05, gen_fn=lambda: gen[0])
        c.put("k", "old")
        time.sleep(0.08)  # past TTL, inside the swr window

        def refresh_with_write():
            gen[0] = 2  # a write lands during the refresh
            return "pre-write"

        v, status = c.get_or_compute("k", refresh_with_write,
                                     swr_s=10.0)
        assert (v, status) == ("old", "stale")
        for _ in range(100):  # wait out the background refresh
            with c._lock:
                if "k" not in c._inflight:
                    break
            time.sleep(0.02)
        assert c.lookup("k") == (False, None)

    def test_disabled_cache_is_transparent(self):
        c = GenCache("t.off", ttl_s=60)
        c.enabled = False
        c.put("k", "v")
        assert c.lookup("k") == (False, None)
        v, status = c.get_or_compute("k", lambda: "computed")
        assert (v, status) == ("computed", "miss")
        assert c.stats()["entries"] == 0

    def test_plane_registry_uniquifies_and_flushes(self):
        c1 = g_cacheplane.register("t.reg", ttl_s=60)
        c2 = g_cacheplane.register("t.reg", ttl_s=60)
        assert c1.name == "t.reg" and c2.name == "t.reg#2"
        c1.put("a", "x" * 100)
        freed = g_cacheplane.flush("t.reg")
        assert freed > 0 and c1.stats()["entries"] == 0
        assert "t.reg" in g_cacheplane.snapshot()


# ---------------------------------------------------------------------------
# membudget integration
# ---------------------------------------------------------------------------

class TestMemoryPressure:
    def test_pressure_sheds_cache_before_refusing_real_work(self):
        """An over-budget pack reservation must empty the cache plane
        rather than be refused — a cache is droppable by definition,
        a query packer's staging arrays are not."""
        cache = g_cacheplane.register("t.pressure", ttl_s=60,
                                      max_entries=256)
        payload = "x" * (64 << 10)
        for i in range(64):
            cache.put(i, payload)
        assert g_membudget.used("cache") >= cache.stats()["bytes"] > 0
        old_limit = g_membudget.limit
        # other tests may have reset() the budget, dropping the
        # plane's weakly-held hook — re-adding is idempotent enough
        g_membudget.add_pressure_handler(g_cacheplane._on_pressure)
        try:
            g_membudget.set_limit(g_membudget.used() + (1 << 20))
            need = 2 << 20  # only fits if the cache plane sheds
            assert g_membudget.reserve("pack", need)
            assert cache.stats()["entries"] == 0
            assert g_membudget.used("cache") < (64 << 10) * 64
            g_membudget.release("pack", need)
        finally:
            g_membudget.set_limit(old_limit)


# ---------------------------------------------------------------------------
# shard-node /rpc/search cache
# ---------------------------------------------------------------------------

class TestShardNodeCache:
    def test_search_cached_and_write_invalidated(self, tmp_path):
        node = cl.ShardNodeServer(tmp_path / "n", port=0)
        for i in range(3):
            node.handle("/rpc/index",
                        {"url": f"http://t.test/n{i}",
                         "content": _doc(i, words="walrus herd")})
        h0 = node._search_cache.hits
        out1 = node.handle("/rpc/search", {"q": "walrus", "topk": 5})
        out2 = node.handle("/rpc/search", {"q": "walrus", "topk": 5})
        assert out2["total"] == out1["total"] == 3
        assert node._search_cache.hits == h0 + 1
        # a write moves posdb.version: the third search recomputes and
        # sees the new doc — no stale window
        node.handle("/rpc/index",
                    {"url": "http://t.test/new",
                     "content": _doc(9, words="walrus herd")})
        out3 = node.handle("/rpc/search", {"q": "walrus", "topk": 5})
        assert out3["total"] == 4
        assert node._search_cache.hits == h0 + 1  # that one missed
        assert out3["gen"] > out1["gen"]

    def test_batched_riders_hit_the_cache(self, tmp_path):
        node = cl.ShardNodeServer(tmp_path / "nb", port=0)
        for i in range(3):
            node.handle("/rpc/index",
                        {"url": f"http://t.test/b{i}",
                         "content": _doc(i, words="ibex ridge")})
        qs = ["ibex", "ridge"]
        node.handle("/rpc/search", {"queries": qs, "topk": 5})
        h0 = node._search_cache.hits
        out = node.handle("/rpc/search", {"queries": qs, "topk": 5})
        assert node._search_cache.hits == h0 + len(qs)
        assert [int(r["total"]) for r in out["results"]] == [3, 3]


# ---------------------------------------------------------------------------
# cluster generations
# ---------------------------------------------------------------------------

class TestClusterGenerations:
    def _cluster(self, tmp_path):
        a = _node(tmp_path, "a")
        b = _node(tmp_path, "b")
        conf = cl.HostsConf.parse(
            f"num-mirrors: 0\n127.0.0.1:{a.port}\n127.0.0.1:{b.port}")
        client = cl.ClusterClient(conf, use_heartbeat=False)
        return a, b, client

    def test_write_on_shard1_keeps_shard0_legs(self, tmp_path):
        a, b, client = self._cluster(tmp_path)
        try:
            # the first scatter's replies fold the node generations in
            # (X-OSSE-Gen); the probed query's legs — captured AFTER
            # that — are stored under the settled generations (a leg's
            # gen is snapped before its RPC, so the very first scatter
            # on a cold client stores already-dead legs by design:
            # correctness over hit rate)
            client.search("token0", topk=5)
            client.search("token1", topk=5)
            keys0 = [k for k in client._leg_cache._d
                     if k[0] == 0 and k[1] == "token1"]
            keys1 = [k for k in client._leg_cache._d
                     if k[0] == 1 and k[1] == "token1"]
            assert keys0 and keys1
            assert client._leg_cache.lookup(
                keys0[0], gen=client.shard_gen(0))[0]
            assert client._leg_cache.lookup(
                keys1[0], gen=client.shard_gen(1))[0]
            gv0 = client.gen_vector()
            # a write routed to shard 1 ...
            u = _url_for_shard(client, 1, "w")
            client.index_document(u, _doc(50))
            _drain(client)
            # ... kills shard 1's legs (local counter bumped BEFORE
            # the send, node gen folded from the write ack) ...
            assert not client._leg_cache.lookup(
                keys1[0], gen=client.shard_gen(1))[0]
            # ... while shard 0's legs stay perfectly live
            assert client._leg_cache.lookup(
                keys0[0], gen=client.shard_gen(0))[0]
            gv1 = client.gen_vector()
            assert gv1[0] == gv0[0]  # shard 0's pair untouched
            assert gv1[1] != gv0[1]  # shard 1's pair moved
            assert gv1[1][0] == gv0[1][0] + 1  # the local half
            assert gv1[1][1] > gv0[1][1]       # the observed-node half
        finally:
            client.close()
            a.stop()
            b.stop()

    def test_result_cache_keys_on_conf_values_not_identity(self, tmp_path):
        """The SERP key must use the conf's PQR factor VALUES, never
        id(conf): CPython reuses freed ids (a new conf could alias a
        dead one's entries), and equal-but-distinct confs should
        share."""
        a, b, client = self._cluster(tmp_path)
        try:
            warm = CollectionConf()
            # first scatter settles the node generations; second fills
            # a live entry under them
            client.search("token0", topk=5, conf=warm)
            client.search("token0", topk=5, conf=warm)
            h0 = client._result_cache.hits
            # a DIFFERENT conf object with equal factors shares it
            client.search("token0", topk=5, conf=CollectionConf())
            assert client._result_cache.hits == h0 + 1
            # changed PQR factors → a distinct entry, not an alias
            client.search("token0", topk=5,
                          conf=CollectionConf(pqr_enabled=False))
            assert client._result_cache.hits == h0 + 1
        finally:
            client.close()
            a.stop()
            b.stop()

    def test_inject_query_delete_query_no_stale_result(self, tmp_path):
        """The acceptance regression: a deleted doc must never ride a
        cached SERP — the generation bump is observed cluster-wide in
        this same test (local half at send time, node half via the
        reply header)."""
        a, b, client = self._cluster(tmp_path)
        try:
            u = _url_for_shard(client, 0, "zeb")
            client.index_document(
                u, _doc(7, words="zebra quagga savanna"))
            _drain(client)
            # the first scatter on a cold client folds the node
            # generations in via X-OSSE-Gen, so its own entry — stamped
            # with the ENTRY-time gen, by design — is already dead
            # (correctness over hit rate); it settles the gens for the
            # searches under test
            client.search("zebra", topk=5)
            res1 = client.search("zebra", topk=5)
            assert res1.total_matches == 1
            assert res1.results[0].url == u
            # second identical query rides the front result cache
            h0 = client._result_cache.hits
            res2 = client.search("zebra", topk=5)
            assert client._result_cache.hits == h0 + 1
            assert res2.results[0].url == u
            gv_before = client.gen_vector()
            client.remove_document(u)
            _drain(client)
            gv_after = client.gen_vector()
            assert gv_after[0] != gv_before[0]       # bump seen
            assert gv_after[0][0] == gv_before[0][0] + 1   # local half
            assert gv_after[0][1] > gv_before[0][1]  # node half (ack)
            # the very next query recomputes: no stale window at all
            res3 = client.search("zebra", topk=5)
            assert res3.total_matches == 0
            assert all(r.url != u for r in res3.results)
        finally:
            client.close()
            a.stop()
            b.stop()


# ---------------------------------------------------------------------------
# serve-plane regression (flat mode)
# ---------------------------------------------------------------------------

class TestServerDeleteRegression:
    def test_inject_query_delete_query(self, tmp_path):
        srv = SearchHTTPServer(str(tmp_path), port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            html = (b"<html><title>D</title><body>"
                    b"<p>ephemeral okapi content</p></body></html>")
            for i in (1, 2):
                urllib.request.urlopen(urllib.request.Request(
                    f"{base}/inject?url=http://d.test/{i}", data=html),
                    timeout=60)
            out = json.load(urllib.request.urlopen(
                f"{base}/search?q=okapi&format=json", timeout=60))
            assert out["totalMatches"] == 2
            h0 = srv.stats.get("result_cache_hits", 0)
            urllib.request.urlopen(f"{base}/search?q=okapi&format=json",
                                   timeout=60)
            assert srv.stats.get("result_cache_hits", 0) == h0 + 1
            # the delete bumps the index generation: the next search
            # MUST NOT serve the cached two-result page
            with urllib.request.urlopen(
                    f"{base}/delete?url=http://d.test/1",
                    timeout=60) as r:
                assert json.load(r)["deleted"] == "http://d.test/1"
            out = json.load(urllib.request.urlopen(
                f"{base}/search?q=okapi&format=json", timeout=60))
            assert out["totalMatches"] == 1
            assert all(res["url"] != "http://d.test/1"
                       for res in out["results"])
            # deleting a url that was never indexed 404s
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"{base}/delete?url=http://d.test/ghost",
                    timeout=60)
        finally:
            srv.stop()

    def test_admin_cache_page_lists_and_flushes(self, tmp_path):
        srv = SearchHTTPServer(str(tmp_path), port=0)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            out = json.load(urllib.request.urlopen(
                f"{base}/admin/cache?format=json", timeout=60))
            assert "server.results" in out["caches"]
            assert out["enabled"] is True
            out = json.load(urllib.request.urlopen(
                f"{base}/admin/cache?flush=all&format=json",
                timeout=60))
            assert "flushed_bytes" in out
        finally:
            srv.stop()
