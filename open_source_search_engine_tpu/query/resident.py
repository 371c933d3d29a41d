"""Resident serving loop — double-buffered device dispatch.

BENCH_r04 pinned single-query p50 at the ~117ms dispatch+fetch RTT:
every ``search_batch`` call paid a full issue→block round trip even
when the device was idle half the time. This loop breaks that floor
the way the reference's UdpServer loop did for network I/O — ONE
always-running consumer owns the device, callers only enqueue:

* ``submit()`` appends to a queue and returns a :class:`Ticket`; it
  never touches jax (no host↔device traffic on the caller's thread —
  the osselint ``device-sync`` rule fences this file).
* The loop thread issues wave N+1 (``DeviceIndex.issue_batch``: plan,
  route, async dispatch — no fetch) while wave N is still computing,
  then collects the oldest in-flight wave (``collect_batch``: the one
  ``device_get`` + escalation reissues). Steady-state dispatch cost is
  one async enqueue; the host sync overlaps the next wave's compute.
* Depth is bounded at :data:`DEPTH` so a burst cannot pipeline
  unbounded device memory.

The loop thread is always in one of three states, each a span of the
trace plane (``utils/trace.py``; on the device trace's clock too while
a profiler session runs): ``resident.idle`` (nothing queued, nothing
in flight), ``resident.issue_wave``, ``resident.collect_wave``. A
ticket's own timeline is four stages of its request's ledger:
``resident.queue_wait`` (submit -> taken), ``resident.issue_wave``,
``resident.inflight_wait`` (issue done -> its collect begins) and
``resident.collect_wave``; every boundary is one clock reading shared
by the stages on both sides, and the devwatch wave record is built
from the same readings.

Freshness protocol (the generation rule the tests pin down): the loop
re-resolves its DeviceIndex via ``di_fn`` ONLY while nothing is in
flight. If ``gen_fn()`` (the Rdb version) moves while waves are in
flight, those waves finish against the base they were issued on — but
the loop drains them all BEFORE refreshing, so any ticket submitted
after the write is guaranteed to be issued against a refreshed base
(``Ticket.generation`` records which). Refreshing mid-flight would be
worse than stale: ``refresh()`` donates the packed buffers a dispatched
wave is still reading.

The issue/collect split is mesh-aware by construction: the loop is
generic over any index duck-typing ``issue_batch(plans, topk, lang) →
pending`` / ``collect_batch(pending)`` / ``_built_version`` (any
equality-comparable value). The single-chip plane drives a
``DeviceIndex``; the mesh serving plane drives a
:class:`~..parallel.sharded.MeshServeIndex`, whose issue dispatches ONE
``shard_map`` program across all chips per ticket wave and whose
generation is the (corpus, serving-topology, per-twin version) tuple —
so a twin death rides the same drain-before-refresh protocol: in-flight
waves finish on the base they were packed from, the next wave packs
from the surviving twin, and no ticket is ever lost to a failover.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable

from ..utils import deadline as deadline_mod
from ..utils import devwatch
from ..utils import threads as _threads
from ..utils import trace
from ..utils.chaos import g_chaos
from ..utils.lockcheck import make_condition, make_event
from ..utils.log import get_logger
from ..utils.membudget import g_membudget
from ..utils.priority import QueueFull
from ..utils.stats import g_stats

log = get_logger("resident")

#: in-flight wave bound: issue N+1 while N computes (double-buffer);
#: deeper pipelines buy nothing once the device is saturated and cost
#: HBM for every staged wave
DEPTH = 2

#: bounded submit queue (admission plane): an overload burst fails
#: fast with QueueFull — counted, charged to the membudget "serve"
#: label — instead of growing host memory without bound
MAX_QUEUE = 1024

#: per-ticket footprint estimate for the membudget gauge (plans list +
#: ticket slots + event)
QUEUE_ENTRY_COST = 2048


class Ticket:
    """One submit()'s handle: wait() blocks until the loop resolves it.

    After resolution, ``di`` is the index the wave actually ran
    against (DeviceIndex or MeshServeIndex) and ``generation`` its
    ``_built_version`` at issue time — callers use ``di`` for
    post-processing (sitehash/langid lookups must come from the same
    snapshot that scored)."""

    __slots__ = ("plans", "topk", "lang", "deadline", "di",
                 "generation", "ledgers", "t_submit", "_ev", "_res",
                 "_err")

    def __init__(self, plans, topk: int, lang: int, deadline=None):
        self.plans = plans
        self.topk = topk
        self.lang = lang
        self.deadline = deadline
        #: the stage ledgers of the requests waiting on this ticket
        #: (the submitter's), and when it was submitted
        self.ledgers = trace.current_ledgers()
        self.t_submit = time.perf_counter()
        self.di = None
        self.generation: int | None = None
        self._ev = make_event("resident.ticket")
        self._res = None
        self._err: BaseException | None = None

    def _resolve(self, res) -> None:
        self._res = res
        self._ev.set()

    def _fail(self, err: BaseException) -> None:
        self._err = err
        self._ev.set()

    def wait(self, timeout: float = 120.0):
        """Block for the wave's raw results ([(docids, scores, n)] per
        plan). Raises the loop's error if the wave failed."""
        if not self._ev.wait(timeout):
            raise TimeoutError("resident loop ticket timed out")
        if self._err is not None:
            raise self._err
        return self._res


class _Wave:
    """An issued-but-uncollected wave and the tickets riding it.
    ``obs`` is the devwatch flight-recorder record opened at issue
    (None when the telemetry plane is off); ``t_begin`` / ``t_issued``
    are the issue span's two clock readings."""

    __slots__ = ("pending", "tickets", "di", "obs", "t_begin",
                 "t_issued")

    def __init__(self, pending, tickets, di, obs=None):
        self.pending = pending
        self.tickets = tickets
        self.di = di
        self.obs = obs
        self.t_begin = self.t_issued = 0.0


def _ledgers_of(tickets) -> tuple:
    """Every rider's stage ledger: a wave's stages are written to all."""
    return tuple(led for t in tickets for led in t.ledgers)


class ResidentLoop:
    """The per-collection dispatch loop (see module docstring).

    ``di_fn`` resolves the current DeviceIndex (and refreshes it when
    the Rdb moved — ``engine.get_device_index``); ``gen_fn`` reads the
    live Rdb version so the loop can detect a mid-flight write without
    touching the index."""

    def __init__(self, di_fn: Callable[[], object],
                 gen_fn: Callable[[], int],
                 max_batch: int = 64, name: str = "coll",
                 max_queue: int = MAX_QUEUE):
        self._di_fn = di_fn
        self._gen_fn = gen_fn
        self.name = name
        self._max_batch = max_batch
        self._max_queue = max_queue
        self._cv = make_condition("resident.cv")
        self._queue: deque[Ticket] = deque()
        self._inflight: deque[_Wave] = deque()
        self._alive = True
        self.waves_issued = 0
        self.drains_for_freshness = 0
        self._thread = _threads.spawn(f"resident-loop-{name}",
                                      self._run)

    @property
    def alive(self) -> bool:
        return self._alive and self._thread.is_alive()

    def submit(self, plans, *, topk: int = 64, lang: int = 0,
               deadline: deadline_mod.Deadline | None = None) -> Ticket:
        """Enqueue compiled plans; returns immediately. The hot path is
        a list append + notify — no device work on this thread. A
        ``deadline`` rides the ticket: the loop abandons the wave before
        issue if the budget ran out while the ticket queued."""
        t = Ticket(list(plans), topk, lang, deadline)
        with self._cv:
            if not self._alive:
                t._fail(RuntimeError("resident loop stopped"))
                return t
            if len(self._queue) >= self._max_queue:
                # bounded admission: fail the ticket, never the loop —
                # the serve edge turns QueueFull into shed-stale-or-503
                g_stats.count("admission.queue_full")
                t._fail(QueueFull("resident loop queue full"))
                return t
            self._queue.append(t)
            self._gauge_locked()
            self._cv.notify_all()
        return t

    def _gauge_locked(self) -> None:
        g_membudget.set_gauge(
            "serve", self, len(self._queue) * QUEUE_ENTRY_COST)

    def stop(self) -> None:
        """Kill the loop; queued and in-flight waiters fail fast."""
        with self._cv:
            self._alive = False
            self._cv.notify_all()

    # ------------------------------------------------------------- loop

    def _run(self) -> None:
        try:
            while True:
                with self._cv:
                    if self._alive and not self._queue \
                            and not self._inflight:
                        # starved: nothing to issue, nothing to collect
                        with trace.timed_span("resident.idle"):
                            while self._alive and not self._queue \
                                    and not self._inflight:
                                self._cv.wait()
                    if not self._alive:
                        self._abort_locked(
                            RuntimeError("resident loop stopped"))
                        return
                if not self._inflight and self._queue:
                    # fill-or-flush: the device is IDLE — launch now
                    # with whatever is queued (a collect window in
                    # front of idle hardware is pure added latency);
                    # while waves are in flight, submitters coalesce
                    # naturally until the pipeline frees a slot
                    g_stats.count("resident.idle_flush")
                if len(self._inflight) < DEPTH:
                    self._issue_one()
                if self._inflight and (
                        len(self._inflight) >= DEPTH
                        or not self._queue):
                    self._collect_one()
        except BaseException as exc:  # noqa: BLE001 — waiters must wake
            log.exception("resident loop died")
            with self._cv:
                self._alive = False
                self._abort_locked(exc)

    def _abort_locked(self, exc: BaseException) -> None:
        for t in self._queue:
            t._fail(exc)
        self._queue.clear()
        self._gauge_locked()
        for w in self._inflight:
            for t in w.tickets:
                t._fail(exc)
        self._inflight.clear()

    def _take_batch(self) -> list[Ticket]:
        """Longest same-(topk, lang) PREFIX of the queue — prefix, not
        filter, so resolution order is exactly submit order."""
        with self._cv:
            if not self._queue:
                return []
            head = self._queue[0]
            batch, nplans = [], 0
            while self._queue and len(batch) < self._max_batch:
                t = self._queue[0]
                if (t.topk, t.lang) != (head.topk, head.lang):
                    break
                if batch and nplans + len(t.plans) > self._max_batch:
                    break
                batch.append(self._queue.popleft())
                nplans += len(t.plans)
            self._gauge_locked()
            return batch

    def _index_for_issue(self):
        """The freshness protocol (module docstring): never re-resolve
        the index while waves are in flight — drain first if the Rdb
        moved, else keep issuing against the in-flight snapshot."""
        if self._inflight:
            di = self._inflight[-1].di
            if self._gen_fn() != di._built_version:
                self.drains_for_freshness += 1
                while self._inflight:
                    self._collect_one()
                return self._di_fn()
            return di
        return self._di_fn()

    def _issue_one(self) -> None:
        batch = self._take_batch()
        if not batch:
            return
        overlapped = bool(self._inflight)
        span = trace.timed_span("resident.issue_wave")
        with trace.bind_ledgers(_ledgers_of(batch)), span:
            # a ticket waited from its submit to this reading, where
            # the wave's issue begins
            for t in batch:
                trace.record("resident.queue_wait", t.t_submit, span.t0,
                             ledgers=t.ledgers)
            wave = self._issue_wave(batch, overlapped, span.t0)
        if wave is not None:
            wave.t_begin, wave.t_issued = span.t0, span.t1

    def _issue_wave(self, batch: list[Ticket], overlapped: bool,
                    t_begin: float):
        """Dispatch one wave for the live tickets of ``batch``; the
        ``_Wave`` now in flight, or None where nothing was issued."""
        live = []
        for t in batch:
            # the coordinator's budget may have run out while the
            # ticket queued — abandon before the device wave, not after
            if deadline_mod.check_abandon("resident.issue", t.deadline):
                t._fail(deadline_mod.DeadlineExceeded(
                    "deadline exceeded before resident issue"))
            else:
                live.append(t)
        batch = live
        if not batch:
            return None
        if g_chaos.enabled:
            g_chaos.resident_fault("issue")
        obs = devwatch.wave_begin("resident", coll=self.name,
                                  tickets=len(batch),
                                  queue=len(self._queue))
        try:
            di = self._index_for_issue()
            plans = [p for t in batch for p in t.plans]
            pending = di.issue_batch(plans, topk=batch[0].topk,
                                     lang=batch[0].lang)
            devwatch.wave_issued(obs, plans=len(plans),
                                 generation=di._built_version)
            for t in batch:
                t.di = di
                t.generation = di._built_version
            wave = _Wave(pending, batch, di, obs)
            self._inflight.append(wave)
            self.waves_issued += 1
            g_stats.count("resident.issue")
            if overlapped:
                g_stats.count("resident.issue_overlapped")
            return wave
        except BaseException as exc:  # noqa: BLE001
            t_fail = time.perf_counter()
            devwatch.wave_end(obs, (t_begin, t_fail, t_fail, t_fail),
                              error=type(exc).__name__)
            for t in batch:
                t._fail(exc)
            return None

    def _collect_one(self) -> None:
        wave = self._inflight.popleft()
        span = trace.timed_span("resident.collect_wave")
        results = err = None
        try:
            with trace.bind_ledgers(_ledgers_of(wave.tickets)), span:
                # the wave waited from its issue's end to this
                # reading, where its collect begins
                trace.record("resident.inflight_wait", wave.t_issued,
                             span.t0)
                if g_chaos.enabled:
                    g_chaos.resident_fault("collect")
                devwatch.wave_collect(wave.obs)
                results = wave.di.collect_batch(wave.pending)
        except BaseException as exc:  # noqa: BLE001
            err = exc
        # the stages are in the riders' ledgers BEFORE a ticket
        # resolves: a woken request may close its timeline at once
        devwatch.wave_end(
            wave.obs, (wave.t_begin, wave.t_issued, span.t0, span.t1),
            error=type(err).__name__ if err else None)
        if err is not None:
            for t in wave.tickets:
                t._fail(err)
            return
        off = 0
        for t in wave.tickets:
            t._resolve(results[off:off + len(t.plans)])
            off += len(t.plans)
