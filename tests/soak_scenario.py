"""The chaos soak scenario: the body of ``test_chaos.py::test_soak_gate``.

Test code, kept beside its one caller as ``sched_scenarios.py`` is: a
whole crawl → index → serve story under the chaos plane, too long for
a test function. Sizes and seed are arguments.
"""

from __future__ import annotations

import os
import random
import time
import types
from datetime import datetime


def run(base_dir: str, n_pages: int = 48, n_queries: int = 160,
        seed: int = 1234) -> dict:
    """Crawl → index → serve end to end on an in-process 2-shard ×
    2-twin cluster under ``base_dir``, with the chaos plane (``seed``)
    injecting the ancestral faults mid-flight. The scenario:

    1. a SpiderLoop crawls a synthetic linked web through the real
       fetch→parse→index pipeline, teeing every page into the cluster;
    2. an open-loop fixed-seed Zipf query load runs while chaos
       delays/refuses one backup twin's legs, kills a primary node
       mid-query (the hedge — not an error retry — must eat it), and a
       slice of the queries carry already-tight deadlines (the
       abandon/degrade path, never the lost path);
    3. the killed node restarts and heartbeats must revive it;
    4. a byte of one node's on-disk posting run is flipped; scrub must
       quarantine the run before any query can read it;
    5. a forced DailyMerge sweep runs under forced memory pressure,
       and the crawl-side grid is rebalanced 1 → 2 shards.

    Returns the report; ``ok`` is true only if EVERY gate holds: the
    crawl completed, zero lost queries, the hedge fired and won over
    the kill, deadline.abandoned > 0, corruption quarantined (detected,
    never served), a merge ran under pressure, the rebalance conserved
    docs, the twin recovered. No gate is a time."""
    from open_source_search_engine_tpu.control.dailymerge import DailyMerge
    from open_source_search_engine_tpu.control.rebalance import rebalance
    from open_source_search_engine_tpu.parallel import cluster as cl
    from open_source_search_engine_tpu.parallel.sharded import (
        ShardedCollection)
    from open_source_search_engine_tpu.spider.fetcher import FetchResult
    from open_source_search_engine_tpu.spider.loop import SpiderLoop
    from open_source_search_engine_tpu.spider.scheduler import (
        SpiderScheduler, UrlFilterRule)
    from open_source_search_engine_tpu.utils import deadline as dlmod
    from open_source_search_engine_tpu.utils.chaos import g_chaos
    from open_source_search_engine_tpu.utils.stats import g_stats

    g_stats.reset()
    g_chaos.disable()

    # --- the cluster: 2 shards × 2 twins (replica-major hosts.conf) ---
    names = ("a0", "b0", "a1", "b1")
    nodes = [cl.ShardNodeServer(os.path.join(base_dir, nm)) for nm in names]
    for n in nodes:
        n.start()
    conf = cl.HostsConf.parse(
        "num-mirrors: 1\n" + "\n".join(
            f"127.0.0.1:{n.port}" for n in nodes))
    client = cl.ClusterClient(conf, use_heartbeat=False)
    client.hostmap.rtt_s[:, 0] = 0.001  # pin replica 0 as primary
    client.hostmap.rtt_s[:, 1] = 0.002

    # --- a synthetic linked web (fixed seed, unique body tokens) ------
    rng = random.Random(6)
    vocab = ["apple", "banana", "cluster", "search", "engine", "chaos",
             "merge", "shard", "twin", "spider", "crawl", "soak"]

    def _url(i: int) -> str:
        return f"http://site{i % 5}.soak.test/p{i}"

    pages = {}
    for i in range(n_pages):
        outl = rng.sample(range(n_pages), min(3, n_pages))
        body = " ".join(rng.choices(vocab, k=24)) + f" token{i}"
        pages[_url(i)] = (
            f"<html><head><title>Soak page {i}</title></head><body>"
            f"<p>{body}</p>"
            + "".join(f'<a href="{_url(j)}">l{j}</a>' for j in outl)
            + "</body></html>")

    class _WebFetcher:
        def fetch_many(self, urls):
            return [FetchResult(url=u, status=200, content=pages[u],
                                content_type="text/html")
                    if u in pages else FetchResult(url=u, status=404)
                    for u in urls]

    local = ShardedCollection("soak", os.path.join(base_dir, "grid1"),
                              n_shards=1)

    class _Target:
        """SpiderLoop's sharded-collection duck type: index into the
        crawl-side grid (link harvest) AND tee into the cluster."""

        def index_document(self, url, content, is_html=True,
                           siterank=0):
            ml = local.index_document(url, content, is_html=is_html,
                                      siterank=siterank)
            if ml is not None:
                client.index_document(url, content)
            return ml

        def site_num_inlinks(self, site):
            return local.site_num_inlinks(site)

    sched = SpiderScheduler(
        filters=[UrlFilterRule("*", delay_s=0.005)],
        resolver=lambda host: host)
    loop = SpiderLoop(_Target(), scheduler=sched, fetcher=_WebFetcher(),
                      batch_size=8)
    for i in range(n_pages):
        loop.add_url(_url(i))
    crawl_stats = loop.crawl(max_pages=n_pages, max_steps=n_pages * 4)

    # two on-disk runs per node so the merge sweep has real work, and
    # everything indexed survives the mid-soak node kill/restart
    for n in nodes:
        n.coll.posdb.dump()
    for i in range(min(6, n_pages)):
        client.index_document(_url(i), pages[_url(i)])
    for n in nodes:
        n.coll.posdb.dump()

    # --- arm chaos, then the open-loop Zipf query load ----------------
    # aim wire faults at b1 (shard 1's backup twin): hedged legs absorb
    # them without query loss
    g_chaos.enable(seed, rate=0.0)
    g_chaos.configure("transport.request", rate=0.15,
                      kinds=("delay", "refuse"),
                      match=f"127.0.0.1:{nodes[3].port}", delay_s=0.01)

    distinct = vocab + [f"token{i}" for i in range(n_pages)]
    zipf = [1.0 / (r + 1) ** 1.1 for r in range(len(distinct))]
    qs = rng.choices(distinct, weights=zipf, k=n_queries)
    kill_at = max(1, n_queries // 3)
    # unique multi-term query: never result-cached, so its scatter leg
    # reaches the doomed primary
    qs[kill_at] = f"cluster token{kill_at % n_pages}"

    lost = degraded = 0
    kill_armed = False
    for k, q in enumerate(qs):
        if k == kill_at:
            g_chaos.configure("cluster.node", rate=1.0, kinds=("kill",),
                              match=str(nodes[0].port), delay_s=0.05)
            kill_armed = True
        dl = None
        if k % 9 == 4:
            # born-tight budget on a never-cached query: must come back
            # degraded (the abandon path), never lost
            dl = dlmod.Deadline.after(0.0003)
            q = f"{q} tight{k}"
        try:
            with dlmod.bind(dl):
                res = client.search(q, topk=10)
        except Exception:  # noqa: BLE001 — a lost query is the bug
            lost += 1
            continue
        if res is None:
            lost += 1
        elif getattr(res, "degraded", False):
            degraded += 1
        if kill_armed and g_chaos.fired("cluster.node").get("kill", 0):
            g_chaos.configure("cluster.node", rate=0.0)  # one kill only
            kill_armed = False
    kill_count = g_chaos.fired("cluster.node").get("kill", 0)
    g_chaos.configure("transport.request", rate=0.0)

    # --- recovery: restart the killed node, heartbeats revive it ------
    restarted = cl.ShardNodeServer(os.path.join(base_dir, "a0"),
                                   port=nodes[0].port)
    give_up = dlmod.Deadline.after(10.0)
    while True:
        try:
            restarted.start()
            break
        except OSError:  # socket still draining from the kill
            if give_up.expired():
                raise
            time.sleep(0.05)
    nodes[0] = restarted
    for _ in range(3):
        client.check_hosts()
    recovered = bool(client.hostmap.alive.all())

    # --- corruption: flip a byte on disk; scrub must trip FIRST -------
    victim = nodes[1].coll.posdb
    flipped = g_chaos.corrupt_one_run(victim)
    quarantined = victim.scrub()
    post = client.search("cluster soak probe", topk=5)
    served_after_scrub = post is not None and not getattr(
        post, "degraded", False)

    # --- forced merge sweep under forced memory pressure --------------
    g_chaos.configure("membudget.reserve", rate=1.0,
                      kinds=("pressure",))
    dm = DailyMerge([n.coll for n in nodes],
                    types.SimpleNamespace(merge_quiet_hours="0-23"),
                    check_interval_s=3600)
    dm.tick(now=datetime(2026, 1, 5, 12, 0))
    g_chaos.configure("membudget.reserve", rate=0.0)
    pressure = g_chaos.fired("membudget.reserve").get("pressure", 0)

    # --- grow the crawl grid: rebalance 1 → 2 shards ------------------
    docs_before = local.num_docs
    grid2 = rebalance("soak", local, os.path.join(base_dir, "grid2"),
                      old_n_shards=1, new_n_shards=2)
    docs_after = grid2.num_docs

    g_chaos.disable()
    c = g_stats.snapshot()["counters"]
    gates = {
        "crawl_complete": crawl_stats.indexed == n_pages,
        "zero_lost_queries": lost == 0,
        "hedge_ate_kill": (kill_count >= 1
                           and c.get("transport.hedge_fired", 0) >= 1
                           and c.get("transport.hedge_won", 0) >= 1),
        "deadline_abandoned": c.get("deadline.abandoned", 0) > 0,
        "corruption_quarantined": (flipped is not None
                                   and len(quarantined) > 0
                                   and c.get("rdb.corrupt_quarantined",
                                             0) >= 1
                                   and served_after_scrub),
        "merge_ran_under_pressure": dm.merges >= 1 and pressure >= 1,
        "rebalance_conserved_docs": (docs_before == docs_after
                                     and docs_before > 0),
        "twin_recovered": recovered,
    }
    ok = all(gates.values())
    keep = ("chaos.", "deadline.", "transport.", "results.", "rdb.",
            "cluster.")
    rep = {
        "ok": ok, "gates": gates, "seed": seed,
        "lost_queries": lost, "degraded_queries": degraded,
        "queries": n_queries, "pages": crawl_stats.indexed,
        "merges": dm.merges,
        "counters": {k: v for k, v in sorted(c.items())
                     if k.startswith(keep)},
    }
    for n in nodes:
        n.stop()
    client.close()
    return rep
