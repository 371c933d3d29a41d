"""Fleet plane tests — REAL OS node processes, fast enough for tier 1.

Covers the FleetManager contract end to end across a true process
boundary: spawn + readiness + seat identity, live parm broadcast
(0x3f semantics: applied everywhere, no restart), SIGKILL + journal
replay rejoin, drain-then-restart through the node admission gate,
and the teardown-hygiene guarantee (zero surviving child pids, even
when the test body raises). With the supervisor on: a primary wedged
(SIGSTOP) and then killed (SIGKILL) through the chaos plane's ``fleet``
seam loses no query and comes back under a new pid with every acked
write; a rolling restart under queries drains and saves every node and
loses none; a shut-down fleet's grid re-shards offline.

Every fixture teardown asserts ``surviving_pids() == []`` — the one
invariant that keeps CI boxes free of orphaned node processes.
"""

import threading

import pytest

from open_source_search_engine_tpu.control.rebalance import rebalance
from open_source_search_engine_tpu.parallel.cluster import ClusterClient
from open_source_search_engine_tpu.parallel.fleet import FleetManager
from open_source_search_engine_tpu.utils.chaos import g_chaos
from open_source_search_engine_tpu.utils.stats import g_stats
from tests.polling import wait_until

DOC = ("<html><head><title>Fleet survivor</title></head><body>"
       "<p>fleet durability words ftoken{i}.</p></body></html>")


def _index(fm, addr, i):
    out = fm.transport.request(
        addr, "/rpc/index",
        {"url": f"http://fleet.test/{i}", "content": DOC.format(i=i)},
        timeout=60.0)
    assert out["ok"], out
    return out


@pytest.fixture
def fleet(tmp_path):
    """One shard, two twins, no supervisor — the tests decide who dies
    and who comes back."""
    fm = FleetManager(tmp_path / "fleet", n_shards=1, n_replicas=2,
                      chaos_seed=5, supervise=False)
    try:
        fm.start_all()
        yield fm
    finally:
        fm.shutdown()
        assert fm.surviving_pids() == []


def test_spawn_readiness_and_identity(fleet):
    fm = fleet
    pids = set()
    for r in range(fm.n_replicas):
        ping = fm.wait_ready(0, r)
        assert ping["ok"] and ping["docs"] == 0
        assert (ping["shard"], ping["replica"]) == (0, r)
        assert ping["draining"] is False
        pids.add(ping["pid"])
    assert len(pids) == fm.n_replicas  # distinct real processes
    # children are spawned with the chaos seed (seams armed, ambient
    # rate 0) and the serialized cluster map
    env = fm._child_env()
    assert env["OSSE_CHAOS"] == "5"
    assert env["OSSE_CHAOS_RATE"] == "0"
    assert fm.hosts_path.read_text()  # hosts.conf handed to every node


def test_parm_broadcast_applies_on_every_node_without_restart(fleet):
    fm = fleet
    pids_before = dict(fm.pids())
    replies = fm.broadcast_parms({"spider_delay_ms": 2718})
    assert len(replies) == fm.n_shards * fm.n_replicas
    for addr, r in replies.items():
        assert r is not None and r["ok"], (addr, r)
        assert "spider_delay_ms" in r["applied"]
        assert r["pid"] == pids_before[
            next(sr for sr in fm.pids()
                 if fm.addr(*sr) == addr)]
    for s in range(fm.n_shards):
        for r in range(fm.n_replicas):
            conf = fm.transport.request(fm.addr(s, r), "/rpc/conf",
                                        {}, timeout=10.0)
            assert conf["conf"]["spider_delay_ms"] == 2718
    assert dict(fm.pids()) == pids_before  # applied live, no restart


def test_sigkill_journal_replay_rejoin(fleet):
    fm = fleet
    for i in range(3):  # write to BOTH twins (the client's fan-out)
        _index(fm, fm.addr(0, 0), i)
        _index(fm, fm.addr(0, 1), i)
    # kill -9 replica 0: no save, no atexit — journals only
    fm.kill(0, 0)
    wait_until(lambda: not fm.alive(0, 0), timeout=10.0,
               desc="node dead after SIGKILL")
    fm.start_node(0, 0, wait=True)
    ping0 = fm.wait_ready(0, 0)
    ping1 = fm.wait_ready(0, 1)
    assert ping0["docs"] == ping1["docs"] == 3  # replay conserved all
    out = fm.transport.request(fm.addr(0, 0), "/rpc/search",
                               {"q": "fleet durability", "topk": 5},
                               timeout=60.0)
    assert out["ok"] and out["total"] == 3
    stats = fm.transport.request(fm.addr(0, 0), "/rpc/stats", {},
                                 timeout=10.0)
    assert stats["ok"] and "stats" in stats


def test_drain_then_restart_through_admission_gate(fleet):
    fm = fleet
    _index(fm, fm.addr(0, 0), 7)
    out = fm.transport.request(fm.addr(0, 0), "/rpc/drain",
                               {"timeout_s": 5.0}, timeout=10.0)
    assert out["ok"] and out["drained"], out
    ping = fm.transport.request(fm.addr(0, 0), "/rpc/ping", {},
                                timeout=10.0)
    assert ping["draining"] is True
    # the gate is closed: data-plane RPCs shed instead of admitting
    shed = fm.transport.request(fm.addr(0, 0), "/rpc/search",
                                {"q": "fleet", "topk": 5},
                                timeout=10.0)
    assert shed["ok"] is False and shed["shed"] == "draining"
    # orderly stop (SIGTERM → save) and rebirth on the same dir
    assert fm.stop_node(0, 0) is not None
    fm.start_node(0, 0, wait=True)
    ping = fm.wait_ready(0, 0)
    assert ping["draining"] is False  # fresh gate
    assert ping["docs"] == 1          # checkpointed state intact


def test_teardown_reaps_even_when_the_body_raises(tmp_path):
    fm = FleetManager(tmp_path / "f2", n_shards=1, n_replicas=1,
                      supervise=False)
    with pytest.raises(RuntimeError, match="boom"):
        with fm:
            assert fm.alive(0, 0)
            raise RuntimeError("boom")
    assert fm.surviving_pids() == []


def test_atexit_reaper_kills_the_process_group(tmp_path):
    """The last-resort finalizer: simulate an owner that never reaches
    shutdown() — _atexit_reap() alone must leave no survivors."""
    fm = FleetManager(tmp_path / "f3", n_shards=1, n_replicas=1,
                      supervise=False)
    fm.start_all()
    assert fm.surviving_pids()
    fm._atexit_reap()
    wait_until(lambda: fm.surviving_pids() == [], timeout=10.0,
               desc="atexit reaper cleared every child")
    fm.shutdown()  # idempotent
    assert fm.surviving_pids() == []


# --- under a client: the supervisor, the chaos seam, the rolling restart ---

def _client_over(fm, n_docs):
    """A ClusterClient over the fleet with ``n_docs`` acked writes, every
    node's query path warmed directly (a first /rpc/search compiles, and
    must inflate neither the hedge timer's EWMA nor a later assertion)
    and replica 0 pinned as each shard's primary."""
    client = ClusterClient(fm.conf, use_heartbeat=False)
    for i in range(n_docs):
        client.index_document(f"http://fleet.test/{i}", DOC.format(i=i))
    assert client.pending_writes == 0  # every twin acked every write
    for addr in fm.addrs():
        out = client.transport.request(
            addr, "/rpc/search", {"q": "fleet durability", "topk": 5},
            timeout=120.0)
        assert out["ok"], out
    client.hostmap.rtt_s[:, 0] = 0.001
    client.hostmap.rtt_s[:, 1] = 0.002
    return client


def _hedges():
    c = g_stats.snapshot()["counters"]
    return (c.get("transport.hedge_fired", 0),
            c.get("transport.hedge_won", 0))


def test_wedged_then_killed_primary_loses_no_query_and_is_respawned(
        tmp_path):
    n_docs = 6
    fm = FleetManager(tmp_path / "sup", n_shards=1, n_replicas=2,
                      chaos_seed=11)  # supervised
    client = None
    g_chaos.enable(11, rate=0.0)  # the parent's seams armed, aimed only
    try:
        fm.start_all()
        client = _client_over(fm, n_docs)
        prey = fm.pid(0, 0)
        fired0, won0 = _hedges()

        # SIGSTOP: the primary's sockets stay open and never answer, so
        # the hedge timer (not an error failover) has to eat each query
        g_chaos.configure("fleet", rate=1.0, kinds=("wedge",))
        assert g_chaos.fleet_fault(prey) == "wedge"
        for i in range(n_docs):  # unique, uncached: each one scatters
            res = client.search(f"durability ftoken{i}", topk=5,
                                site_cluster=False)
            assert not res.degraded and res.total_matches == 1, i
        fired, won = _hedges()
        assert fired > fired0 and won > won0

        # SIGKILL for real: no save, no atexit, journals only
        g_chaos.configure("fleet", rate=1.0, kinds=("kill",))
        assert g_chaos.fleet_fault(prey) == "kill"
        res = client.search("fleet durability words", topk=10,
                            site_cluster=False)
        assert not res.degraded and res.total_matches == n_docs

        # the supervisor respawns the seat; journal replay conserves
        # every acked write on it
        reborn = fm.wait_ready(0, 0, timeout_s=60.0)
        twin = fm.wait_ready(0, 1)
        assert reborn["pid"] != prey
        assert reborn["docs"] == twin["docs"] == n_docs
        # a scrape is a read, not a liveness verdict: the first one may
        # ride a pooled socket that died with the old process
        wait_until(lambda: all(w is not None for w in
                               client.scrape()["hosts"].values()),
                   timeout=15.0, interval=0.25,
                   desc="scrape sees every host up after the respawn")
    finally:
        g_chaos.disable()
        if client is not None:
            client.close()
        fm.shutdown()
    assert fm.surviving_pids() == []


def test_rolling_restart_under_queries_drains_saves_and_loses_none(fleet):
    fm = fleet
    n_docs = 6
    client = _client_over(fm, n_docs)
    counts = {"ok": 0, "degraded": 0, "lost": 0}
    done = threading.Event()

    def load():
        k = 0
        while not done.is_set():
            k += 1
            try:
                res = client.search(f"durability ftoken{k % n_docs} q{k}",
                                    topk=5, site_cluster=False)
                counts["degraded" if res.degraded else "ok"] += 1
            except Exception:  # noqa: BLE001 — a lost reply is the bug
                counts["lost"] += 1

    th = threading.Thread(target=load, daemon=True)
    th.start()
    try:
        pids_before = dict(fm.pids())
        roll = fm.rolling_restart(drain_timeout_s=5.0)
    finally:
        done.set()
        th.join(60.0)
        client.close()
    assert [n["node"] for n in roll["nodes"]] == ["s0r0", "s0r1"]
    assert all(n["drained"] and n["saved"] for n in roll["nodes"]), roll
    assert all(fm.pids()[sr] != pids_before[sr] for sr in pids_before)
    assert counts["ok"] > 0, counts
    assert counts["lost"] == 0 and counts["degraded"] == 0, counts
    for r in range(fm.n_replicas):  # the checkpoints held everything
        assert fm.wait_ready(0, r)["docs"] == n_docs


def test_a_shut_down_fleets_grid_reshards_offline(tmp_path):
    """The fleet's base dir is a ShardedCollection grid: what the node
    processes saved on their way down re-shards 2 -> 3 with every
    document kept."""
    n_docs = 9
    grid = tmp_path / "grid"
    with FleetManager(grid, n_shards=2, n_replicas=1,
                      supervise=False) as fm:
        client = ClusterClient(fm.conf, use_heartbeat=False)
        try:
            for i in range(n_docs):
                client.index_document(f"http://fleet.test/{i}",
                                      DOC.format(i=i))
            assert client.pending_writes == 0
        finally:
            client.close()
    assert fm.surviving_pids() == []
    sc = rebalance("shard", grid, tmp_path / "regrid", 2, 3)
    assert sc.num_docs == n_docs
