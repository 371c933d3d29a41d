"""Resident serving loop — ordering, freshness, and one-shot parity.

The loop's contract (query/resident.py): submit() is a pure enqueue;
results come back for exactly the plans submitted, in submit order; a
write landing while waves are in flight drains those waves against
their issue-time base and every LATER submit is issued against a
refreshed index (Ticket.generation proves which base scored it).
"""

import threading
import time

import numpy as np
import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query import engine
from open_source_search_engine_tpu.query.engine import (
    _compile_cached, get_device_index, get_resident_loop,
    search_device_batch)
from open_source_search_engine_tpu.query.resident import ResidentLoop
from open_source_search_engine_tpu.utils.stats import g_stats

from .polling import wait_until

DOCS = {
    "http://a.example.com/fruit": """
      <html><head><title>Fruit basics</title></head><body>
      <p>The apple is sweet. A banana is tropical. Apple pie wins.</p>
      </body></html>""",
    "http://b.example.com/apple": """
      <html><head><title>Apple orchard</title></head><body>
      <p>Our orchard grows apple trees. Apple harvest is in fall.</p>
      </body></html>""",
    "http://c.example.org/banana": """
      <html><head><title>Banana farm</title></head><body>
      <p>Banana plantations export banana bunches worldwide.</p>
      </body></html>""",
    "http://d.example.org/cellar": """
      <html><head><title>Vegetables</title></head><body>
      <p>Carrots and beets. Root cellar storage tips.</p></body></html>""",
}

QUERIES = ["apple", "banana", "apple banana", "fruit", "cellar",
           "orchard apple", "zeppelin"]


@pytest.fixture()
def coll(tmp_path):
    c = Collection("res", tmp_path)
    c.conf.pqr_enabled = False
    for u, h in DOCS.items():
        docproc.index_document(c, u, h)
    return c


def _key(r):
    return (-round(r.score, 3), r.docid)


class TestParity:
    def test_resident_matches_one_shot_batch(self, coll):
        """CPU parity: the loop's issue/collect split must reproduce
        one-shot search_device_batch exactly (same plans, same index
        snapshot → same docids and scores)."""
        one_shot = search_device_batch(coll, QUERIES, topk=10,
                                       site_cluster=False)
        res = search_device_batch(coll, QUERIES, topk=10,
                                  site_cluster=False, resident=True)
        for q, a, b in zip(QUERIES, one_shot, res):
            assert b.total_matches == a.total_matches, q
            assert sorted(map(_key, b.results)) == \
                   sorted(map(_key, a.results)), q

    def test_raw_ticket_matches_search_batch(self, coll):
        di = get_device_index(coll)
        plans = [_compile_cached(q, 0) for q in QUERIES]
        ref = di.search_batch(plans, topk=64, lang=0)
        loop = get_resident_loop(coll)
        got = loop.submit(plans, topk=64, lang=0).wait()
        assert len(got) == len(ref)
        for q, (rd, rs, rn), (gd, gs, gn) in zip(QUERIES, ref, got):
            assert gn == rn, q
            assert list(gs) == list(rs), q


class TestOrdering:
    def test_concurrent_submits_get_their_own_results(self, coll):
        """16 threads × 4 rounds enqueue distinct queries concurrently;
        every ticket must resolve to ITS query's results (no swaps, no
        cross-wave mixups), matching a one-shot reference."""
        di = get_device_index(coll)
        ref = {}
        for q in QUERIES:
            plan = _compile_cached(q, 0)
            ((d, s, n),) = di.search_batch([plan], topk=64, lang=0)
            ref[q] = (sorted(d.tolist()), n)
        loop = get_resident_loop(coll)
        errors = []
        start = threading.Barrier(16)

        def worker(i):
            try:
                start.wait(timeout=30)
                for r in range(4):
                    q = QUERIES[(i + r) % len(QUERIES)]
                    t = loop.submit([_compile_cached(q, 0)],
                                    topk=64, lang=0)
                    ((d, s, n),) = t.wait(timeout=60)
                    assert (sorted(d.tolist()), n) == ref[q], q
            except BaseException as exc:  # noqa: BLE001
                errors.append((i, exc))

        ts = [threading.Thread(target=worker, args=(i,), daemon=True)
              for i in range(16)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        assert loop.waves_issued >= 1

    def test_one_submit_many_plans_keeps_plan_order(self, coll):
        loop = get_resident_loop(coll)
        plans = [_compile_cached(q, 0) for q in QUERIES]
        got = loop.submit(plans, topk=64, lang=0).wait()
        di = get_device_index(coll)
        ref = di.search_batch(plans, topk=64, lang=0)
        for (rd, rs, rn), (gd, gs, gn) in zip(ref, got):
            assert gn == rn and list(gs) == list(rs)


class TestFreshness:
    def test_write_bumps_generation_and_serves_fresh(self, coll):
        """A submit after a write must be issued against a refreshed
        base: the new doc is visible and Ticket.generation moved past
        the pre-write generation — the loop never reuses the pre-write
        packed base for post-write tickets."""
        loop = get_resident_loop(coll)
        t0 = loop.submit([_compile_cached("apple", 0)], topk=64, lang=0)
        t0.wait(timeout=60)
        gen0 = t0.generation
        assert gen0 == t0.di._built_version

        docproc.index_document(
            coll, "http://e.example.com/durian",
            "<html><title>Durian</title><body>"
            "<p>The durian fruit is pungent.</p></body></html>")
        assert coll.posdb.version != gen0  # the write moved the Rdb

        t1 = loop.submit([_compile_cached("durian", 0)], topk=64,
                         lang=0)
        ((docids, scores, n),) = t1.wait(timeout=60)
        assert n >= 1 and len(docids) >= 1  # fresh doc is searchable
        assert t1.generation != gen0
        assert t1.generation == t1.di._built_version

    def test_midflight_write_drains_before_refresh(self, coll):
        """Drive the loop's freshness branch directly: with a wave in
        flight, a generation move forces a drain of the old-base waves
        before any new issue — the in-flight ticket keeps its issue
        generation, the post-write ticket gets the new one."""
        di = get_device_index(coll)
        gens = [di._built_version]

        def di_fn():
            return get_device_index(coll)

        def gen_fn():
            return coll.posdb.version

        loop = ResidentLoop(di_fn, gen_fn, name="midflight")
        try:
            plan = _compile_cached("banana", 0)
            first = loop.submit([plan], topk=64, lang=0)
            first.wait(timeout=60)
            docproc.index_document(
                coll, "http://f.example.com/mango",
                "<html><title>Mango</title><body>"
                "<p>Mango season, mango juice.</p></body></html>")
            # burst of submits racing the version bump: every ticket
            # must still score consistently with ITS recorded base
            tickets = [loop.submit([_compile_cached("mango", 0)],
                                   topk=64, lang=0) for _ in range(6)]
            for t in tickets:
                t.wait(timeout=60)
            # the last ticket was certainly issued post-write (the
            # submits happened after index_document returned)
            last = tickets[-1]
            assert last.generation == coll.posdb.version
            ((d, s, n),) = last.wait()
            assert n >= 1
            assert gens[0] != last.generation
        finally:
            loop.stop()


class TestLifecycle:
    def test_stop_fails_fast_and_loop_respawns(self, coll):
        loop = get_resident_loop(coll)
        loop.submit([_compile_cached("apple", 0)], topk=64,
                    lang=0).wait(timeout=60)
        loop.stop()
        t = loop.submit([_compile_cached("apple", 0)], topk=64, lang=0)
        with pytest.raises(RuntimeError):
            t.wait(timeout=10)
        # engine hands out a fresh loop once the old one is dead
        loop2 = get_resident_loop(coll)
        assert loop2 is not loop and loop2.alive
        ((d, s, n),) = loop2.submit(
            [_compile_cached("apple", 0)], topk=64, lang=0
        ).wait(timeout=60)
        assert n >= 1


class _FakeIndex:
    """The loop's duck type, nothing else: ``issue_batch`` hands the
    plans back, ``collect_batch`` blocks on the first wave only (so a
    test can queue behind it) and echoes every plan as a result."""

    _built_version = 0

    def __init__(self):
        self.hold = threading.Event()
        self.collecting = threading.Event()
        self.collected = 0

    def issue_batch(self, plans, topk=64, lang=0):
        return list(plans)

    def collect_batch(self, pending):
        if self.collected == 0:
            self.collecting.set()
            self.hold.wait(60)
        self.collected += 1
        return [(p, None, 0) for p in pending]


def _resident_counters():
    """(waves issued, of them with another wave in flight), so far."""
    c = g_stats.snapshot()["counters"]
    return (c.get("resident.issue", 0),
            c.get("resident.issue_overlapped", 0))


class TestTimeline:
    def test_issue_overlapped_counts_issues_with_a_wave_in_flight(self):
        """One ticket a wave (``max_batch`` 1). Wave 1 is issued alone
        and its collect is held; three tickets queue behind it. Once
        released the loop issues wave 2 with nothing in flight, then
        waves 3 and 4 each beside the wave before: 4 issues, 2 of them
        overlapped — exactly."""
        di = _FakeIndex()
        i0, o0 = _resident_counters()
        loop = ResidentLoop(lambda: di, lambda: 0, max_batch=1,
                            name="overlap")
        try:
            first = loop.submit(["w1"], topk=8)
            assert di.collecting.wait(60)
            queued = [loop.submit([f"w{k}"], topk=8) for k in (2, 3, 4)]
            di.hold.set()
            for t in [first] + queued:
                assert t.wait(timeout=60)[0][0] == t.plans[0]
        finally:
            di.hold.set()
            loop.stop()
        i1, o1 = _resident_counters()
        assert (i1 - i0, o1 - o0) == (4, 2)

    def test_a_ticket_carries_its_submitters_ledgers(self):
        """The four stages of a ticket's timeline land, in order, in
        the ledger bound on the submitting thread — written by the
        loop's thread, before the ticket resolves."""
        from open_source_search_engine_tpu.utils import trace
        di = _FakeIndex()
        di.hold.set()
        loop = ResidentLoop(lambda: di, lambda: 0, name="ledger")
        led = trace.StageLedger()
        try:
            with trace.bind_ledgers((led,)):
                t = loop.submit(["p"], topk=8)
            t.wait(timeout=60)
            assert [n for n, _ in led.rows] == [
                "resident.queue_wait", "resident.issue_wave",
                "resident.inflight_wait", "resident.collect_wave"]
            assert all(ms >= 0.0 for _, ms in led.rows)
        finally:
            loop.stop()


# ---------------------------------------------------------------------------
# host and device do not take turns (PR 31): the server's batcher keeps
# 2 * DEPTH batches out and nothing before the loop's queue takes the
# server's core lock, so the loop has a ticket queued when it collects
# ---------------------------------------------------------------------------

class _SleepyIndex:
    """The loop's duck type with a device that takes its time: an
    issue costs the host ``ISSUE_S``, waves run on the device one after
    another for ``DEVICE_S`` each, a collect blocks until its wave is
    done. No jax anywhere."""

    _built_version = 0
    ISSUE_S, DEVICE_S = 0.002, 0.012

    def __init__(self):
        self.ready = 0.0
        self.collected = 0

    def issue_batch(self, plans, topk=64, lang=0):
        time.sleep(self.ISSUE_S)
        self.ready = max(self.ready, time.perf_counter()) + self.DEVICE_S
        return self.ready, list(plans)

    def collect_batch(self, pending):
        ready, plans = pending
        time.sleep(max(0.0, ready - time.perf_counter()))
        self.collected += 1
        none = np.zeros(0, np.int64)
        return [(none, np.zeros(0, np.float32), 1) for _ in plans]

    def sitehash_of(self, docids):
        return np.zeros(len(docids), np.uint32)

    langid_of = sitehash_of


@pytest.fixture()
def sleepy_server(tmp_path, monkeypatch):
    """A ``SearchHTTPServer`` (not listening) whose batches ride the
    real path (``QueryBatcher`` -> ``_run_device_batch`` ->
    ``search_device_batch(resident=True, results_lock=server.core)``
    -> a real ``ResidentLoop``) over a ``_SleepyIndex``, one query a
    batch, with a results tail that holds the core lock for
    ``tail_s``: shorter than a wave, as where the device sets the
    pace."""
    from open_source_search_engine_tpu.serve.server import (
        SearchHTTPServer)
    tail_s = 0.005
    srv = SearchHTTPServer(tmp_path, port=0)
    srv._batcher.MAX_B = 1
    di = _SleepyIndex()
    # one plan a wave, as a toy-cell FD query rides a program alone
    loop = ResidentLoop(lambda: di, lambda: 0, max_batch=1,
                        name="sleepy")
    monkeypatch.setattr(engine, "get_resident_loop",
                        lambda coll, deadline=None, warm=False: loop)

    def slow_tail(get_doc, docids, scores, plan, **kw):
        time.sleep(tail_s)
        return [], 0

    monkeypatch.setattr(engine, "build_results", slow_tail)
    monkeypatch.setattr(engine, "site_column", lambda di, docids: [])
    try:
        yield srv, loop, di
    finally:
        loop.stop()
        srv._batcher.stop()


class TestHostAndDeviceOverlap:
    def test_wave_n_plus_1_is_issued_under_wave_ns_results_tails(
            self, sleepy_server):
        """Twelve closed-loop clients, ten queries each: more than half
        of the waves are issued while another is in flight. With two
        batches out and the lookup under the core lock (the parent) it
        was one wave in a hundred here. Bounded by its joins."""
        srv, loop, di = sleepy_server
        errors = []

        def client(i):
            try:
                for k in range(10):
                    res = srv._batcher.search(("main", 10, 0),
                                              f"client{i} query{k}",
                                              timeout=60)
                    assert res.total_matches == 1
            except BaseException as exc:  # noqa: BLE001
                errors.append((i, exc))

        i0, o0 = _resident_counters()
        ts = [threading.Thread(target=client, args=(i,), daemon=True)
              for i in range(12)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        assert not any(t.is_alive() for t in ts)
        i1, o1 = _resident_counters()
        assert i1 - i0 == 120       # one query a batch, one a wave
        assert (o1 - o0) / (i1 - i0) > 0.5, (i1 - i0, o1 - o0)

    def test_a_batch_is_submitted_while_the_core_lock_is_held(
            self, sleepy_server):
        """Another thread holds ``server.core`` (an inject, a results
        tail): a batch still reaches the loop and its wave is issued
        and collected; only its results tail waits for the lock."""
        srv, loop, di = sleepy_server
        held, release = threading.Event(), threading.Event()

        def holder():
            with srv._lock:
                held.set()
                release.wait(60)

        h = threading.Thread(target=holder, daemon=True)
        h.start()
        got = []
        rider = threading.Thread(
            target=lambda: got.append(srv._batcher.search(
                ("main", 10, 0), "under the lock", timeout=60)),
            daemon=True)
        try:
            assert held.wait(60)
            rider.start()
            wait_until(lambda: di.collected == 1, timeout=60,
                       desc="the wave collected under a held core lock")
            assert loop.waves_issued == 1 and not got
        finally:
            release.set()
        rider.join(60)
        h.join(60)
        assert [r.total_matches for r in got] == [1]


class TestBatchesOut:
    def test_no_more_than_twice_depth_out_errors_reach_riders_and_stop_fails_the_queue(  # noqa: E501
            self):
        """The batcher's bound on batches out is ``2 * DEPTH`` (the
        pool's workers, each living one batch); a failing batch fails
        each of its riders and no other; ``stop()`` fails what is still
        queued. Bounded by its waits."""
        from open_source_search_engine_tpu.query.resident import DEPTH
        from open_source_search_engine_tpu.serve.server import (
            QueryBatcher)
        release = threading.Event()
        gauge = threading.Lock()
        out = {"now": 0, "most": 0, "ran": []}

        def run_batch(key, queries):
            with gauge:
                out["now"] += 1
                out["most"] = max(out["most"], out["now"])
                out["ran"].append(key)
            try:
                release.wait(60)
                if key == "boom":
                    raise RuntimeError("kernel on fire")
                return [q.upper() for q in queries]
            finally:
                with gauge:
                    out["now"] -= 1

        def rider(key, q, into):
            try:
                into.append(b.search(key, q, timeout=60))
            except BaseException as exc:  # noqa: BLE001
                into.append(exc)

        def ride(key, q):
            into = []
            t = threading.Thread(target=rider, args=(key, q, into),
                                 daemon=True)
            t.start()
            return t, into

        b = QueryBatcher(run_batch)
        b.MAX_B, b.WINDOW_S = 3, 60.0
        try:
            # an idle batcher launches the first at once; with a batch
            # out it collects MAX_B same-key riders: three ride "boom"
            first = ride("k0", "a")
            wait_until(lambda: out["ran"] == ["k0"], desc="first batch")
            boom = [ride("boom", q) for q in "xyz"]
            wait_until(lambda: "boom" in out["ran"], desc="boom batch")
            # ... and the window never closes for lone riders: cut it
            b.WINDOW_S = 0.0
            rest = [ride(f"k{i}", "b") for i in range(1, 3 * DEPTH + 1)]
            wait_until(lambda: out["now"] == 2 * DEPTH,
                       desc="every worker holds a batch")
            wait_until(lambda: b._inflight == 2 + 3 * DEPTH,
                       desc="every batch handed to the pool")
            assert out["most"] == 2 * DEPTH
            # a long window again: the next riders stay in the queue
            b.WINDOW_S = 60.0
            queued = [ride("late", q) for q in "pq"]
            wait_until(lambda: len(b._queue) == 2, desc="two queued")
            b.stop()
            for t, into in queued:
                t.join(60)
                assert isinstance(into[0], RuntimeError) \
                    and "stopped" in str(into[0])
            release.set()
            for t, into in [first] + boom + rest:
                t.join(60)
                assert not t.is_alive()
        finally:
            release.set()
            b.stop()
        assert out["most"] == 2 * DEPTH
        assert first[1] == ["A"]
        for t, into in boom:
            assert isinstance(into[0], RuntimeError) \
                and "kernel on fire" in str(into[0])
        assert [into for t, into in rest] == [["B"]] * (3 * DEPTH)
