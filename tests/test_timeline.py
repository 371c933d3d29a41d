"""A request's timeline from inside the program — the stage ledger.

Every /search request carries a flat ledger of (stage, ms) for the
disjoint stages of ``trace.REQUEST_STAGES``; ``trace.record`` and
``trace.timed_span`` are the only doors into it. These tests are
decided by events and counts, never by a wall-clock threshold: which
stages a request leaves and in what order, that a coalesced batch's
stages reach every rider, that a held pool shows as ``pool_wait``,
that the slow log carries the ledger and bounds its appends, and that
a span lands on the profiler's clock where (and only where) jax is
there to take it.
"""

import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

import pytest

from open_source_search_engine_tpu.query.resident import DEPTH
from open_source_search_engine_tpu.serve import serve
from open_source_search_engine_tpu.serve.server import QueryBatcher
from open_source_search_engine_tpu.utils import trace as tm
from open_source_search_engine_tpu.utils.stats import g_stats

from .polling import wait_until

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC = ("<html><head><title>Solar panels guide</title></head><body>"
       "<p>Solar panels convert sunlight into electricity.</p>"
       "</body></html>")

#: what one request through the device path leaves, in order; the core
#: lock is taken once on the batch path (the results tail: the
#: collection lookup has the registry's own lock since PR 31)
ONE_REQUEST = list(tm.REQUEST_STAGES)


def _count(name: str) -> int:
    lat = g_stats.snapshot()["latencies"].get(name)
    return lat["count"] if lat else 0


def _counter(name: str) -> int:
    return g_stats.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# one request, front door to last byte
# ---------------------------------------------------------------------------

def test_one_request_leaves_each_stage_once_in_order(tmp_path,
                                                     monkeypatch):
    closed = []
    finish = tm.finish_request

    def spy(ledger, t0, t1=None):
        t1 = time.perf_counter() if t1 is None else t1
        finish(ledger, t0, t1)
        closed.append((ledger, (t1 - t0) * 1000.0))

    monkeypatch.setattr(tm, "finish_request", spy)
    srv = serve(tmp_path, port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/inject?u=http://s.example/g",
            data=DOC.encode())
        urllib.request.urlopen(req).read()
        url = f"http://127.0.0.1:{srv.port}/search?q="
        urllib.request.urlopen(url + "sunlight").read()    # cold start
        wait_until(lambda: len(closed) == 1, desc="first request closed")
        before = {s: _count(s) for s in tm.REQUEST_STAGES}
        body = json.loads(urllib.request.urlopen(url + "solar").read())
        assert body["totalMatches"] == 1
        wait_until(lambda: len(closed) == 2, desc="request closed")
    finally:
        srv.stop()
    ledger, request_ms = closed[1]
    names = [n for n, _ in ledger.rows]
    # each stage once: the core lock's one acquisition is the tail's
    assert names == ONE_REQUEST                     # in order
    assert list(ledger.stages()) == ONE_REQUEST
    assert all(ms >= 0.0 for _, ms in ledger.rows)
    staged = sum(ms for n, ms in ledger.rows if n != "serve.unaccounted")
    assert staged <= request_ms
    assert ledger.stages()["serve.unaccounted"] == \
        pytest.approx(request_ms - staged, abs=1e-6)
    # g_stats saw the same: one more of each, the lock's too
    for s in tm.REQUEST_STAGES:
        assert _count(s) - before[s] == 1, s


# ---------------------------------------------------------------------------
# the batcher: riders, the pool
# ---------------------------------------------------------------------------

class _Rider(threading.Thread):
    """A request's thread: binds its own ledger, asks the batcher."""

    def __init__(self, batcher, key, q):
        super().__init__(daemon=True)
        self.batcher, self.key, self.q = batcher, key, q
        self.ledger = tm.StageLedger()
        self.res = None
        self.start()

    def run(self):
        with tm.bind_ledgers((self.ledger,)):
            self.res = self.batcher.search(self.key, self.q, timeout=60)


def test_three_riders_of_one_batch_each_hold_its_stages():
    release = threading.Event()
    batches = []

    def run_batch(key, queries):
        batches.append(list(queries))
        if key == "first":
            release.wait(60)        # holds a wave in flight
        else:
            # a batch stage, written once on the pool thread
            tm.record("query.results_work", time.perf_counter())
        return [q.upper() for q in queries]

    b = QueryBatcher(run_batch)
    # with a batch in flight the loop collects until MAX_B are queued
    # (the window is an upper bound this test never reaches)
    b.MAX_B, b.WINDOW_S = 3, 60.0
    try:
        first = _Rider(b, "first", "a")
        wait_until(lambda: batches, desc="first batch on the pool")
        riders = [_Rider(b, "k", q) for q in ("x", "y", "z")]
        wait_until(lambda: len(batches) == 2, desc="the coalesced batch")
        release.set()
        for r in riders + [first]:
            r.join(60)
    finally:
        release.set()
        b.stop()
    assert sorted(batches[1]) == ["x", "y", "z"]
    for r in riders:
        assert r.res == r.q.upper()
        assert list(r.ledger.stages()) == [
            "batcher.queue_wait", "batcher.pool_wait",
            "query.results_work", "batcher.wake"], r.ledger.rows
    # the batch's stages are ONE measurement, fanned out
    for stage in ("batcher.pool_wait", "query.results_work"):
        assert len({r.ledger.stages()[stage] for r in riders}) == 1
    # ... and each rider's own wait is its own
    assert first.ledger.stages()["batcher.queue_wait"] >= 0.0


def test_a_held_pool_shows_as_pool_wait():
    release = threading.Event()
    running = []
    workers = 2 * DEPTH     # every pool thread lives one batch

    def run_batch(key, queries):
        running.append(key)
        if key != "last":
            release.wait(60)
        return list(queries)

    b = QueryBatcher(run_batch)
    try:
        held = []
        for i in range(workers):
            held.append(_Rider(b, f"held{i}", "a"))
            wait_until(lambda: len(running) == i + 1,
                       desc=f"pool thread {i + 1} held")
        formed = _count("batcher.queue_wait")
        last = _Rider(b, "last", "c")
        wait_until(lambda: _count("batcher.queue_wait") == formed + 1,
                   desc="last batch formed")
        t_hold = time.perf_counter()    # formed, and no thread to run it
        time.sleep(0.05)
        assert "last" not in running and len(running) == workers
        hold_ms = (time.perf_counter() - t_hold) * 1000.0
        release.set()
        for r in held + [last]:
            r.join(60)
    finally:
        release.set()
        b.stop()
    assert last.res == "c"
    assert last.ledger.stages()["batcher.pool_wait"] >= hold_ms


# ---------------------------------------------------------------------------
# the slow log
# ---------------------------------------------------------------------------

def test_slow_unsampled_line_holds_stages_and_appends_are_bounded(
        tmp_path):
    path = tmp_path / "slowlog.jsonl"
    tr = tm.Tracer(sample_n=10 ** 9, slow_ms=1e-9)
    tr.configure(slowlog_path=path)
    clock = [100.0]
    tr._clock = lambda: clock[0]
    dropped0 = _counter("trace.slow_dropped")
    n = tm.SLOWLOG_PER_S + 6
    for _ in range(n):
        with tr.start("search", q="x") as t:
            assert not t.sampled
            tm.record("serve.render", time.perf_counter())
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == tm.SLOWLOG_PER_S       # the bound, to the line
    assert _counter("trace.slow_dropped") - dropped0 == 6
    for line in lines:
        assert line["slow"] and not line["sampled"]
        assert list(line["stages"]) == ["serve.render"]
        assert line["stages"]["serve.render"] >= 0.0
    assert len(tr.ring) == tm.SLOWLOG_PER_S     # the ring is not flooded
    clock[0] += 1.0                             # the next second
    with tr.start("search", q="y"):
        pass
    assert len(path.read_text().splitlines()) == tm.SLOWLOG_PER_S + 1


def test_a_sampled_trace_is_kept_past_the_slow_bound():
    tr = tm.Tracer(sample_n=1, slow_ms=1e-9)
    tr._clock = lambda: 7.0
    for _ in range(tm.SLOWLOG_PER_S + 3):
        with tr.start("search"):
            pass
    assert len(tr.ring) == tm.SLOWLOG_PER_S + 3


# ---------------------------------------------------------------------------
# the profiler's clock
# ---------------------------------------------------------------------------

def test_timed_span_without_jax_opens_no_annotation(monkeypatch):
    monkeypatch.setattr(tm, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", None)
    n0 = _count("resident.idle")
    span = tm.timed_span("resident.idle")
    with span:
        pass
    assert span._ann is None and tm._annotation is None
    assert _count("resident.idle") == n0 + 1
    assert span.t1 >= span.t0


def test_timed_span_opens_an_annotation_only_while_a_session_runs(
        monkeypatch):
    seen, session = [], [False]

    class Annotation:
        def __init__(self, name):
            self.name = name
            seen.append(("made", name))

        @staticmethod
        def is_enabled():
            return session[0]

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    fake = types.SimpleNamespace(
        profiler=types.SimpleNamespace(TraceAnnotation=Annotation))
    monkeypatch.setattr(tm, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", fake)
    with tm.timed_span("resident.collect_wave"):    # no session: nothing
        pass
    assert seen == []
    session[0] = True
    with tm.timed_span("resident.collect_wave"):
        seen.append("body")
        session[0] = False      # the session ends inside the span
    assert seen == [("made", "resident.collect_wave"),
                    ("enter", "resident.collect_wave"), "body",
                    ("exit", "resident.collect_wave")]
    with tm.timed_span("resident.idle"):
        pass
    assert len(seen) == 4


def test_a_jax_without_a_profiler_is_no_error(monkeypatch):
    monkeypatch.setattr(tm, "_annotation", None)
    monkeypatch.setitem(sys.modules, "jax", types.SimpleNamespace())
    with tm.timed_span("resident.idle"):
        pass
    assert tm._annotation is False


#: run in a process of its own: a profiler session started in a test
#: worker that has loaded the TPU library (tests/test_tpu_compile.py
#: describes a v5e there) waits for a chip that is not attached
_RECORD = r"""
import glob, json, sys, urllib.request
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from open_source_search_engine_tpu.serve import serve
from open_source_search_engine_tpu.utils import trace as tm

base, doc = sys.argv[1], sys.argv[2]


def host_events(tag, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(f"{base}/{tag}", profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(f"{base}/{tag}/plugins/profile/*/*.xplane.pb")
    return sorted({e.name for p in ProfileData.from_file(xplane).planes
                   if p.name.startswith("/host:")
                   for ln in p.lines for e in ln.events})


def two_spans():
    with tm.timed_span("resident.issue_wave"):
        jnp.ones(8).sum().block_until_ready()
    with tm.timed_span("resident.collect_wave"):
        pass


out = {"spans": host_events("spans", two_spans)}
srv = serve(f"{base}/srv", port=0)
try:
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/inject?u=http://s.example/g",
        data=doc.encode())
    urllib.request.urlopen(req).read()
    url = f"http://127.0.0.1:{srv.port}/search?q="
    urllib.request.urlopen(url + "sunlight").read()    # cold start
    out["request"] = host_events(
        "request", lambda: urllib.request.urlopen(url + "solar").read())
finally:
    srv.stop()
print("HOST_EVENTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two small traces recorded on the CPU and read the way the
    benchmark's ``trace_reduce`` reads one: the names of the events in
    the ``.xplane.pb``'s host planes."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "-c", _RECORD,
         str(tmp_path_factory.mktemp("xplane")), DOC],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    (line,) = [x for x in p.stdout.splitlines()
               if x.startswith("HOST_EVENTS ")]
    return json.loads(line.split(" ", 1)[1])


def test_spans_lie_in_the_host_plane_of_a_recorded_trace(recorded):
    """The program's span names are events of the host plane, beside
    JAX's own."""
    assert {"resident.issue_wave", "resident.collect_wave"} \
        <= set(recorded["spans"])


def test_a_served_request_under_the_profiler_names_every_thread(
        recorded):
    """One /search answered while a profiler session runs: the
    handler's ``serve.search``, the pool thread's
    ``query.device_batch`` and results tail, and the loop's issue and
    collect all lie in the host plane of the same ``.xplane.pb``."""
    assert {"serve.search", "query.device_batch", "query.results_batch",
            "resident.issue_wave", "resident.collect_wave",
            "serve.render"} <= set(recorded["request"])


# ---------------------------------------------------------------------------
# the ledger itself
# ---------------------------------------------------------------------------

def test_only_stages_enter_a_ledger_and_a_trace_takes_up_the_bound_one():
    led = tm.StageLedger()
    tr = tm.Tracer(sample_n=10 ** 9, slow_ms=1e9)
    with tm.bind_ledgers((led,)):
        with tr.start("search") as t:
            assert t.ledger is led
            with tm.timed_span("serve.search"):         # a container
                tm.record("devindex.plan", time.perf_counter())
                tm.record("query.lock_wait", time.perf_counter())
                tm.record("query.lock_wait", time.perf_counter())
    assert [n for n, _ in led.rows] == ["query.lock_wait"] * 2
    assert list(led.stages()) == ["query.lock_wait"]
    assert tm.current_ledgers() == ()
    # a caller that is its own front door gets a ledger of the trace's
    with tr.start("search") as t:
        assert tm.current_ledgers() == (t.ledger,)
    assert tm.current_ledgers() == ()
