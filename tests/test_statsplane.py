"""Metrics-plane tests: mergeable log-linear histograms, the 2-shard
fleet scrape, SLO error budgets under a chaos latency wedge, and the
exemplar-linked /admin/perf + /metrics surfacing."""

import json
import random
import re
import urllib.request

import pytest

from open_source_search_engine_tpu.utils import stats as stats_mod
from open_source_search_engine_tpu.utils.slo import SloTracker
from open_source_search_engine_tpu.utils.stats import (LatencyStat,
                                                       Stats, g_stats,
                                                       merge_wire)

#: one bucket's relative error (1/_SUB) plus interpolation slack
REL_ERR = 1.0 / stats_mod._SUB + 0.02


def _true_quantile(vals, q):
    vs = sorted(vals)
    return vs[min(len(vs) - 1, int(q * len(vs)))]


class TestHistogram:
    def test_sub_ms_samples_resolve_below_1ms(self):
        # the old log2 floor reported 1.0ms for ANY sub-ms sample
        st = LatencyStat()
        for _ in range(200):
            st.add(0.003)
        assert 0.0025 < st.quantile(0.5) < 0.0035
        assert st.to_dict()["p99_ms"] < 0.01

    def test_quantile_interpolates_within_bucket(self):
        # 70ms everywhere must report ~70, not the 128 the old
        # bucket-upper-bound answer gave
        st = LatencyStat()
        for _ in range(100):
            st.add(70.0)
        assert abs(st.quantile(0.99) - 70.0) / 70.0 <= REL_ERR

    @pytest.mark.parametrize("seed", [1, 7, 42])
    def test_merge_matches_combined_stream(self, seed):
        rng = random.Random(seed)
        vals = [rng.lognormvariate(1.0, 2.0) for _ in range(4000)]
        cut = rng.randrange(1, len(vals) - 1)
        a, b, both = LatencyStat(), LatencyStat(), LatencyStat()
        for v in vals[:cut]:
            a.add(v)
        for v in vals[cut:]:
            b.add(v)
        for v in vals:
            both.add(v)
        a.merge(b)
        assert a.count == len(vals)
        for q in (0.5, 0.9, 0.99):
            # merged == the recorder that saw the whole stream...
            assert abs(a.quantile(q) - both.quantile(q)) < 1e-9
            # ...and both track the exact stream within one bucket
            true = _true_quantile(vals, q)
            assert abs(a.quantile(q) - true) / true <= REL_ERR, q

    def test_wire_roundtrip_and_merge_wire(self):
        ga, gb = Stats(), Stats()
        rng = random.Random(3)
        vals = [rng.uniform(0.1, 50.0) for _ in range(600)]
        for v in vals[:300]:
            ga.record_ms("m", v)
        for v in vals[300:]:
            gb.record_ms("m", v)
        ga.count("c", 2)
        gb.count("c", 5)
        gb.gauge("g", 7.0)
        # wire forms must survive JSON (what /rpc/stats actually ships)
        wires = [json.loads(json.dumps(ga.wire())),
                 json.loads(json.dumps(gb.wire()))]
        fleet = merge_wire(wires)
        assert fleet["counters"]["c"] == 7
        assert fleet["gauges"]["g"] == 7.0
        st = fleet["latencies"]["m"]
        assert st.count == 600
        true = _true_quantile(vals, 0.99)
        assert abs(st.quantile(0.99) - true) / true <= REL_ERR

    def test_count_over(self):
        st = LatencyStat()
        for v in (1.0, 2.0, 100.0, 200.0):
            st.add(v)
        assert st.count_over(50.0) == 2
        assert st.count_over(0.001) == 4
        assert st.count_over(1e9) == 0

    def test_exemplar_pins_to_bucket(self):
        st = LatencyStat()
        st.add(5.0)
        st.add(500.0, exemplar="t-slow")
        idx = stats_mod._bucket_index(500.0)
        assert st.exemplars[idx][0] == "t-slow"
        # merge carries exemplars across
        other = LatencyStat()
        other.merge(st)
        assert other.exemplars[idx][0] == "t-slow"

    def test_reset_preserves_gauges(self):
        g = Stats()
        g.count("c")
        g.record_ms("l", 5.0)
        g.gauge("pool_size", 16.0)
        g.reset()
        snap = g.snapshot()
        assert snap["counters"] == {} and snap["latencies"] == {}
        assert snap["gauges"] == {"pool_size": 16.0}
        g.reset_gauges()
        assert g.snapshot()["gauges"] == {}


class TestSlo:
    def test_burn_and_recovery_with_injected_clock(self):
        reg = Stats()
        slo = SloTracker(registry=reg)
        slo.declare_latency("query_p99", "q", threshold_ms=100.0,
                            target=0.9, window_s=60.0)
        now = 1000.0
        for _ in range(50):
            reg.record_ms("q", 5.0)
        st = slo.evaluate(now=now)["query_p99"]
        assert st["burn_rate"] == 0.0 and st["budget_remaining"] == 1.0
        assert not slo.degraded()
        # the wedge: everything over threshold
        for _ in range(50):
            reg.record_ms("q", 500.0)
        st = slo.evaluate(now=now + 1)["query_p99"]
        assert st["burn_rate"] > 1.0
        assert slo.degraded() and slo.degraded("query_p99")
        assert reg.snapshot()["gauges"]["slo.query_p99.burn_rate"] > 1.0
        # recovery: fault gone, window rolls past the bad deltas
        for _ in range(50):
            reg.record_ms("q", 5.0)
        st = slo.evaluate(now=now + 120.0)["query_p99"]
        assert st["burn_rate"] <= 1.0
        assert not slo.degraded()
        assert reg.snapshot()["gauges"]["slo.degraded"] == 0.0

    def test_availability_objective(self):
        reg = Stats()
        slo = SloTracker(registry=reg)
        slo.declare_availability("avail", "rpc.ok", "rpc.err",
                                 target=0.999, window_s=60.0)
        reg.count("rpc.ok", 999)
        st = slo.evaluate(now=10.0)["avail"]
        assert st["burn_rate"] == 0.0
        reg.count("rpc.err", 10)
        st = slo.evaluate(now=11.0)["avail"]
        assert st["burn_rate"] > 1.0


def _mk_cluster(tmp_path, n_nodes=2, docs_per_node=6):
    from open_source_search_engine_tpu.parallel import cluster as cl
    nodes = []
    for i in range(n_nodes):
        node = cl.ShardNodeServer(tmp_path / f"n{i}")
        for d in range(docs_per_node):
            node.handle("/rpc/index", {
                "url": f"http://t.test/{i}-{d}",
                "content": (f"<html><body><p>alpha bravo words "
                            f"token{i}x{d}</p></body></html>")})
        node.start()
        nodes.append(node)
    conf = cl.HostsConf.parse(
        "num-mirrors: 0\n"
        + "\n".join(f"127.0.0.1:{n.port}" for n in nodes))
    client = cl.ClusterClient(conf, use_heartbeat=False)
    return nodes, client


class TestFleetScrape:
    def test_two_shard_scrape_matches_ground_truth(self, tmp_path):
        nodes, client = _mk_cluster(tmp_path)
        try:
            # private per-node registries: in one process both nodes
            # would otherwise serve the same g_stats singleton and the
            # merge would be the singleton merged with itself
            for n in nodes:
                n.stats_registry = Stats()
            rng = random.Random(11)
            ground = LatencyStat()
            vals = []
            for n in nodes:
                n.stats_registry.count("node.queries", 100)
                for _ in range(400):
                    v = rng.lognormvariate(1.5, 1.2)
                    vals.append(v)
                    n.stats_registry.record_ms("node.query", v)
                    ground.add(v)
            sc = client.scrape()
            assert all(w is not None for w in sc["hosts"].values())
            fleet = sc["fleet"]
            assert fleet["counters"]["node.queries"] == 200
            st = fleet["latencies"]["node.query"]
            assert st.count == 800
            for q in (0.5, 0.99):
                # merged fleet == ground-truth single recorder...
                assert abs(st.quantile(q) - ground.quantile(q)) < 1e-9
                # ...and the exact stream within one bucket's error
                true = _true_quantile(vals, q)
                assert abs(st.quantile(q) - true) / true <= REL_ERR
        finally:
            client.close()
            for n in nodes:
                n.stop()

    def test_dead_host_scrapes_as_none(self, tmp_path):
        nodes, client = _mk_cluster(tmp_path)
        try:
            nodes[1].stop()
            # generous timeout: the live host must answer even on a
            # loaded CI box — the DEAD host is detected by refusal
            # (closed port), not by racing this budget
            sc = client.scrape(timeout=2.0)
            vals = list(sc["hosts"].values())
            assert sum(1 for w in vals if w is None) == 1
            assert sum(1 for w in vals if w is not None) == 1
        finally:
            client.close()
            nodes[0].stop()

    def test_chaos_wedge_burns_budget_then_recovers(self, tmp_path):
        from open_source_search_engine_tpu.utils.chaos import g_chaos
        nodes, client = _mk_cluster(tmp_path)
        slo = SloTracker(registry=g_stats)
        now = 5000.0
        try:
            # warm the stack (JAX compiles, pools), then drop the
            # warmup latencies so only steady-state samples are judged
            for k in range(8):
                client.search(f"alpha warm{k}", topk=5)
            g_stats.reset()
            for k in range(20):
                client.search(f"alpha h{k}", topk=5)
            # the threshold comes from the healthy latency this box
            # just showed (a fixed 30 ms judged the box, not the
            # arithmetic: a CPU shared by six xdist workers read
            # healthy queries over it), and the wedge from the
            # threshold: healthy samples sit far under it, wedged ones
            # well over, whatever the machine's speed today
            with g_stats._lock:
                healthy_max = g_stats.latencies["cluster.query"].max_ms
            threshold_ms = 5.0 * max(healthy_max, 1.0)
            slo.declare_latency("query_p99", "cluster.query",
                                threshold_ms=threshold_ms, target=0.95,
                                window_s=60.0)
            st = slo.evaluate(now=now)["query_p99"]
            assert st["window_total"] == 20 and st["window_bad"] == 0, st
            assert st["burn_rate"] <= 1.0, st
            # the wedge: every node leg slowwalks well past threshold
            g_chaos.enable(4242, rate=0.0)
            g_chaos.configure("cluster.node", rate=1.0,
                              kinds=("slowwalk",),
                              delay_s=2.0 * threshold_ms / 1000.0)
            for k in range(10):
                client.search(f"alpha w{k}", topk=5)
            assert g_chaos.fired("cluster.node").get("slowwalk", 0) > 0
            st = slo.evaluate(now=now + 1)["query_p99"]
            assert st["burn_rate"] > 1.0, st
            assert slo.degraded() and slo.degraded("query_p99")
            gauges = g_stats.snapshot()["gauges"]
            assert gauges["slo.query_p99.burn_rate"] > 1.0
            # fault removed: fresh healthy traffic + the window
            # rolling past the wedge recovers the budget
            g_chaos.disable()
            for k in range(20):
                client.search(f"alpha r{k}", topk=5)
            st = slo.evaluate(now=now + 120.0)["query_p99"]
            assert st["burn_rate"] <= 1.0, st
            assert not slo.degraded()
        finally:
            g_chaos.disable()
            client.close()
            for n in nodes:
                n.stop()


DOC = ("<html><head><title>Perf page</title></head><body>"
       "<p>solar panels convert sunlight efficiently</p></body></html>")


@pytest.fixture()
def server(tmp_path):
    from open_source_search_engine_tpu.serve import serve
    s = serve(tmp_path / "srv", port=0)
    yield s
    s.stop()


def _get(server, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{path}") as r:
        return r.status, r.read().decode(), r.headers.get_content_type()


class TestPerfSurfacing:
    def test_perf_metrics_json_and_exemplar_resolves(self, server):
        from open_source_search_engine_tpu.utils.trace import (
            DEFAULT_SAMPLE_N, g_tracer)
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}"
            "/inject?u=http://perf.example.com/p", data=DOC.encode())
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        g_tracer.configure(sample_n=1)
        try:
            for k in range(4):
                _get(server, f"/search?q=sunlight+x{k}")
        finally:
            g_tracer.configure(sample_n=DEFAULT_SAMPLE_N)

        # /admin/perf?format=json: merged view with exemplars
        _, body, ctype = _get(server, "/admin/perf?format=json")
        assert ctype == "application/json"
        perf = json.loads(body)
        lat = perf["fleet"]["latencies"]["serve.search"]
        assert lat["count"] >= 4
        assert lat["exemplars"], "sampled traces must pin exemplars"
        tid = lat["exemplars"][-1]["trace_id"]

        # the exemplar trace id resolves on /admin/traces
        status, tbody, _ = _get(server, f"/admin/traces?id={tid}")
        assert status == 200 and tid in tbody

        # /admin/perf HTML: fleet table + a live exemplar link
        _, html, ctype = _get(server, "/admin/perf")
        assert ctype == "text/html"
        assert "serve.search" in html and "fleet" in html
        m = re.search(r'href="/admin/traces\?id=([a-f0-9]+)', html)
        assert m is not None
        status, _, _ = _get(server, f"/admin/traces?id={m.group(1)}")
        assert status == 200

        # /metrics: Prometheus exposition with histogram + exemplar
        _, text, ctype = _get(server, "/metrics")
        assert ctype == "text/plain"
        assert 'osse_latency_ms_bucket{name="serve.search"' in text
        assert "trace_id=" in text
        assert 'osse_counter{name="query"}' in text
