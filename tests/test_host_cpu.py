"""Where the host's CPU goes: the process's CPU account by thread role
(``utils.threads``: ``host.cpu_us.<role>``), the CPU time of the spans
of ``trace.CPU_SPANS`` (``<name>.cpu_us``), the batcher's count of its
riders' wakes (``batcher.rider_wake``), and the benchmark's eight
readers of them. Thresholds are CPU seconds a test spends itself, with
room for what the rest of the process does meanwhile."""

import importlib.util
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler
from pathlib import Path

import pytest

from open_source_search_engine_tpu.serve import serve
from open_source_search_engine_tpu.serve import server as server_mod
from open_source_search_engine_tpu.serve.server import QueryBatcher
from open_source_search_engine_tpu.utils import threads
from open_source_search_engine_tpu.utils import trace as tm
from open_source_search_engine_tpu.utils.stats import g_stats

from .polling import wait_until

READERS = Path(__file__).resolve().parents[1] / "benchmarks" / \
    "layer_metrics"


def _counter(name: str) -> int:
    return g_stats.snapshot()["counters"].get(name, 0)


def _spin(seconds: float) -> None:
    """Burn ``seconds`` of this thread's CPU."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# ---------------------------------------------------------------------------
# the CPU account by role
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name, role", [
    ("resident-loop-main", "loop"),
    ("query-batcher", "batcher"),
    ("query-pool_3", "pool"),
    ("Thread-7 (process_request_thread)", "front"),
    ("MainThread", "other"),
    ("httpd-8000", "other"),
    ("statsdb", "other"),
    ("query-batcher-2", "other"),
])
def test_a_thread_role_is_read_from_its_name(name, role):
    assert threads.role_of(name) == role


@pytest.mark.parametrize("role, name", [
    ("loop", "resident-loop-cpu-test"),
    ("batcher", "query-batcher"),
    ("pool", "query-pool-cpu-test"),
])
def test_a_spinning_role_thread_raises_its_role_and_a_sleeper_does_not(
        role, name):
    counter = f"host.cpu_us.{role}"
    spun, sleeping, release = threading.Event(), threading.Event(), \
        threading.Event()

    def spinner():
        _spin(0.05)
        spun.set()
        release.wait(30)

    def sleeper():
        sleeping.set()
        release.wait(30)

    try:
        before = _counter(counter)
        t = threads.spawn(name, spinner)
        assert spun.wait(30)
        # read while the thread lives: a long-lived role is read from
        # the thread's own clock at the snapshot
        spun_us = _counter(counter) - before
        s = threads.spawn(name, sleeper)
        assert sleeping.wait(30)
        before = _counter(counter)
        time.sleep(0.1)
        slept_us = _counter(counter) - before
    finally:
        release.set()
    t.join(30)
    s.join(30)
    assert spun_us >= 40_000, spun_us
    assert slept_us < 10_000, slept_us


def test_the_account_forgets_an_ended_thread_and_publishes_deltas():
    acct = threads.CpuAccount()
    acct.poll()
    release = threading.Event()
    t = threads.spawn("query-pool-forget-test", release.wait, 30)
    acct.poll()
    assert t in acct._last
    release.set()
    t.join(30)
    got = acct.poll()
    assert t not in acct._last
    # deltas: the process clock never runs backwards, nothing negative
    assert all(v > 0 for v in got.values())
    assert set(got) <= {f"host.cpu_us.{r}" for r in threads.ROLES}


def test_other_is_the_process_clock_less_the_named_roles():
    acct = threads.CpuAccount()
    got = acct.poll()
    total = sum(got.values()) / 1e6
    # the first poll publishes the process's whole CPU so far
    assert total == pytest.approx(time.process_time(), abs=0.05)


class _SpinHandler(BaseHTTPRequestHandler):
    held = threading.Event()
    release = threading.Event()
    cpu = []

    def do_GET(self):
        _spin(0.03)
        type(self).held.set()
        type(self).release.wait(30)
        type(self).cpu.append(time.thread_time())
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"ok")

    def log_message(self, *a):
        pass


def test_a_handler_thread_adds_its_cpu_once_at_its_end(monkeypatch):
    calls = []
    add = threads.g_cpu.add_front

    def counted():
        calls.append(threading.current_thread().name)
        add()

    monkeypatch.setattr(threads.g_cpu, "add_front", counted)
    httpd = server_mod._FrontDoorHTTPServer(("127.0.0.1", 0), _SpinHandler)
    serving = threads.spawn("httpd-cpu-test", httpd.serve_forever)
    try:
        before = _counter("host.cpu_us.front")
        got = []
        client = threads.spawn("client-cpu-test", lambda: got.append(
            urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_address[1]}/",
                timeout=30).read()))
        assert _SpinHandler.held.wait(30)
        # spun 30 ms and still running: nothing added yet
        assert _counter("host.cpu_us.front") == before
        assert calls == []
        _SpinHandler.release.set()
        client.join(30)
        assert got == [b"ok"]
        wait_until(lambda: calls, desc="the handler thread's end")
        added = _counter("host.cpu_us.front") - before
        assert _counter("host.cpu_us.front") - before == added
    finally:
        _SpinHandler.release.set()
        httpd.shutdown()
        httpd.server_close()
        serving.join(30)
    assert len(calls) == 1
    assert threads.role_of(calls[0]) == "front"
    assert _SpinHandler.cpu[0] * 1e6 <= added
    assert added < _SpinHandler.cpu[0] * 1e6 + 20_000


def test_the_roles_reach_the_wire_and_metrics(tmp_path):
    srv = serve(tmp_path, port=0)
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        urllib.request.urlopen(url).read()
        wait_until(lambda: _counter("host.cpu_us.front"),
                   desc="a handler's cpu added")
        body = urllib.request.urlopen(url).read().decode()
    finally:
        srv.stop()
    for role in ("batcher", "front", "other"):
        assert f'osse_counter{{name="host.cpu_us.{role}"}}' in body
    assert "host.cpu_us.other" in g_stats.wire()["counters"]


# ---------------------------------------------------------------------------
# time off the CPU inside a span
# ---------------------------------------------------------------------------

def test_cpu_spans_are_the_three_named():
    assert tm.CPU_SPANS == ("resident.issue_wave", "devindex.plan",
                            "query.results_work")


@pytest.mark.parametrize("work, low, high", [
    (lambda: time.sleep(0.05), 0, 5_000),
    (lambda: _spin(0.03), 29_000, None),
])
def test_a_cpu_span_counts_its_threads_cpu(work, low, high):
    name = "resident.issue_wave"
    before = _counter(f"{name}.cpu_us")
    sp = tm.timed_span(name)
    with sp:
        work()
    cpu_us = _counter(f"{name}.cpu_us") - before
    wall_us = (sp.t1 - sp.t0) * 1e6
    assert low <= cpu_us <= (high if high is not None else wall_us)
    assert cpu_us <= wall_us


def test_a_span_off_the_list_counts_no_cpu():
    name = "resident.collect_wave"
    with tm.timed_span(name):
        _spin(0.005)
    tm.record(name, time.perf_counter(), cpu0=time.thread_time())
    assert f"{name}.cpu_us" not in g_stats.snapshot()["counters"]


@pytest.mark.parametrize("name", ["devindex.plan", "query.results_work"])
def test_a_recorded_cpu_span_counts_from_the_callers_reading(name):
    before = _counter(f"{name}.cpu_us")
    t0, cpu0 = time.perf_counter(), time.thread_time()
    _spin(0.02)
    time.sleep(0.02)
    tm.record(name, t0, cpu0=cpu0)
    cpu_us = _counter(f"{name}.cpu_us") - before
    assert 19_000 <= cpu_us < 30_000
    # without the caller's reading the span is timed as before
    tm.record(name, time.perf_counter())
    assert _counter(f"{name}.cpu_us") - before == cpu_us


# ---------------------------------------------------------------------------
# the batcher's rider wakes
# ---------------------------------------------------------------------------

def test_rider_wakes_count_every_return_from_a_riders_wait():
    seen: dict[str, int] = {}
    lock = threading.Lock()

    def run_batch(key, queries):
        time.sleep(0.02)    # the other riders park meanwhile
        return [q.upper() for q in queries]

    b = QueryBatcher(run_batch)
    wait = b._cv.wait

    def counted(timeout=None):
        r = wait(timeout)
        me = threading.current_thread().name
        if me.startswith("rider-"):
            with lock:
                seen[me] = seen.get(me, 0) + 1
        return r

    b._cv.wait = counted
    res = {}

    def rider(i):
        res[i] = b.search(f"k{i % 4}", f"q{i}", timeout=60)

    before = _counter("batcher.rider_wake")
    try:
        riders = [threads.spawn(f"rider-{i}", rider, i) for i in range(8)]
        for r in riders:
            r.join(60)
    finally:
        b.stop()
    assert res == {i: f"Q{i}" for i in range(8)}
    assert len(seen) == 8 and min(seen.values()) >= 1
    assert _counter("batcher.rider_wake") - before == sum(seen.values())


# ---------------------------------------------------------------------------
# the benchmark's readers
# ---------------------------------------------------------------------------

def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", READERS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


WIN = {"rows": [], "open": 3.0, "close": 23.0, "seconds": 20.0,
       "loop": "closed", "timeout_s": 30.0}
#: a window of 20 s: 1,000 answers, 30 CPU seconds of which 12 the
#: loop's; 400 waves holding 400 plans; 250 batches; 2,500 rider wakes
COUNTERS = {
    "query": 1000,
    "host.cpu_us.loop": 12e6, "host.cpu_us.batcher": 1e6,
    "host.cpu_us.pool": 9e6, "host.cpu_us.front": 5e6,
    "host.cpu_us.other": 3e6,
    "resident.issue_wave.count": 400, "resident.issue_wave.total_ms": 6000.0,
    "resident.issue_wave.cpu_us": 4e6,
    "devindex.plan.count": 400, "devindex.plan.total_ms": 2000.0,
    "devindex.plan.cpu_us": 1.6e6,
    "query.results_work.count": 250, "query.results_work.total_ms": 5000.0,
    "query.results_work.cpu_us": 2.5e6,
    "batcher.wake.count": 1000, "batcher.wake.total_ms": 3000.0,
    "batcher.rider_wake": 2500,
}
#: what the parent of these counters records: spans and ``query``
PARENT = {k: v for k, v in COUNTERS.items()
          if not k.startswith("host.") and not k.endswith(".cpu_us")
          and k != "batcher.rider_wake"}


@pytest.mark.parametrize("name, value", [
    ("host_cpu_share", 150.0),          # 30 s of CPU in 20 s
    ("host_cpu_ms", 30.0),              # 30,000 ms / 1,000 answers
    ("loop_cpu_share", 60.0),
    ("issue_offcpu_ms", 15.0 - 10.0),   # wall mean less CPU mean
    ("plan_offcpu_ms", 5.0 - 4.0),
    ("tail_offcpu_ms", 20.0 - 10.0),
    ("wake_ms", 3.0),
    ("rider_wakes", 2.5),
])
def test_a_reader_by_hand_and_nothing_without_its_counters(name, value):
    read = _reader(name)
    assert read({"counters": COUNTERS, "win": WIN}) == pytest.approx(value)
    assert read({"counters": {}, "win": WIN}) is None
    assert read({"counters": PARENT, "win": WIN}) is None


def test_cpu_a_query_and_the_cpu_share_agree():
    ctx = {"counters": COUNTERS, "win": WIN}
    share = _reader("host_cpu_share")(ctx)
    per_query = _reader("host_cpu_ms")(ctx)
    assert per_query * COUNTERS["query"] / (WIN["seconds"] * 1000.0) \
        * 100.0 == pytest.approx(share)
    assert _reader("host_cpu_share")(
        {"counters": COUNTERS, "win": {**WIN, "seconds": 0}}) is None
