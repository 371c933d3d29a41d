"""Distributed query tracing — sampled span trees across shards.

The reference engine answers "why was this query slow?" with per-stage
timing logged inside ``PosdbTable::intersectLists10_r`` and at the
Msg39/Msg3a boundary.  Once a query fans out over the hedged cluster
transport that style of logging stops composing — the interesting time
is on another host, inside a hedge attempt that may not even have won.
This module is the Dapper-style fix (Sigelman et al., 2010):

* **Span trees** — ``g_tracer.start(name)`` opens a trace whose root
  span rides a :mod:`contextvars` context; ``span(name, **tags)``
  context managers hang child spans off whatever span is current.
  Timestamps come from the monotonic ``time.perf_counter`` clock and
  serialize as millisecond offsets from the trace start.
* **Head-based sampling** — the keep/drop decision is made once, at
  trace start (``trace_sample`` parm, default 1 in 64).  Unsampled
  traces still time their root (so the slow-query net below works) but
  every ``span()`` inside them is a no-op: the unsampled path must be
  cheap enough to leave on in production (what tracing costs when on
  is the benchmark's ``traced_qps`` beside its ``qps``).
* **Slow-query log** — any trace slower than ``slow_query_ms`` is kept
  regardless of the sampling coin flip and appended as one JSON line to
  ``slowlog.jsonl`` (next to ``statsdb.jsonl``).  An unsampled slow
  trace keeps its root-span skeleton and its **stage ledger**
  (``"stages"``): where the time went, hand-off by hand-off, as far
  as the trace's own end (``serve.unaccounted`` is recorded after the
  last byte is written, when the line is already out).  Appends
  are bounded (:data:`SLOWLOG_PER_S` a second, the rest counted in
  ``trace.slow_dropped``): a saturated server, where every request is
  slow, must not pay a file line a request.
* **Stage ledger** — always on, sampled or not: every request carries
  a flat list of (stage, ms) for the disjoint stages of
  :data:`REQUEST_STAGES`.  It is bound by a contextvar on the
  handler's thread, handed across threads explicitly like the parent
  span (queue entry, ``Ticket``), and a coalesced batch's stages fan
  out to every rider.  ``record`` / ``timed_span`` are the only doors:
  they feed ``g_stats`` (always), the ledger (stages), the span tree
  (sampled) and, while a ``jax.profiler`` session runs, a
  ``TraceAnnotation`` of the same name on the device trace's clock.
* **Time off the CPU** — a span of :data:`CPU_SPANS` also reads its
  thread's CPU clock at both ends and counts the difference as
  ``<name>.cpu_us``: its wall time less that is time its thread was
  ready or blocked, not running (waiting for the interpreter lock
  looks like running to a wall clock).
* **Cross-host propagation** — the transport stamps outgoing RPCs with
  an ``X-OSSE-Trace: <trace_id>:<parent_span_id>`` header; node
  handlers ``adopt()`` it, run their handler under a local root span,
  and ship the finished subtree back inside the reply (``"_trace"``
  key).  The client-side RPC span ``graft()``\\ s that subtree so the
  coordinator ends up holding ONE tree spanning every host the query
  touched.  Remote offsets are rebased onto the local RPC span's start,
  so cross-host clock skew never enters the picture (the network time
  shows up as the gap between the RPC bar and its remote children).

Threads are the sharp edge: a fresh ``threading.Thread`` starts with an
EMPTY contextvars context, so the trace does NOT follow work into
thread pools or hedge threads on its own.  Pass the parent span
explicitly (``begin(name, parent=sp)``) or re-attach it in the worker
(``with attach(sp): ...``) — the cluster client and batchers do both.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import sys
import threading
import time
import uuid
from collections import deque

from .log import get_logger
from .stats import g_stats

log = get_logger("perf")

#: HTTP header carrying "<trace_id>:<parent_span_id>" across hosts
TRACE_HEADER = "X-OSSE-Trace"
#: finished sampled/slow traces kept in memory for /admin/traces
RING_KEEP = 128
#: default head-sampling rate: keep 1 trace in N (0 disables tracing)
DEFAULT_SAMPLE_N = 64
#: default slow-query threshold (ms); slower traces always kept
DEFAULT_SLOW_MS = 1000.0
#: slow traces exported (ring + slowlog.jsonl) in any one second; the
#: rest are counted in ``trace.slow_dropped``
SLOWLOG_PER_S = 4

#: the stages of a served request in the order it meets them. They are
#: disjoint and together cover ``serve.request``; only these names
#: enter a stage ledger. Containers (``serve.request``,
#: ``serve.search``, ``query.device_batch``, ``query.results_batch``)
#: and what a stage holds (``devindex.plan`` / ``.issue`` / ``.device``)
#: stay spans and ``g_stats`` timings, and are never summed.
REQUEST_STAGES = (
    "admission.queue_delay",    # admit entered -> admitted
    "batcher.queue_wait",       # enqueued -> its batch formed
    "batcher.pool_wait",        # batch formed -> a pool thread runs it
    "resident.queue_wait",      # submit -> taken by the loop
    "resident.issue_wave",      # plan, route, async dispatch
    "resident.inflight_wait",   # issue done -> its collect begins
    "resident.collect_wave",    # blocking fetch, escalation rounds
    "query.lock_wait",          # the results tail waits for the core
                                # lock, its one acquisition a batch
    "query.results_work",       # results built under the core lock
    "batcher.wake",             # result set -> the rider's thread runs
    "serve.render",             # render_results
    "serve.unaccounted",        # serve.request less all of the above
)
_STAGE_SET = frozenset(REQUEST_STAGES)

#: the spans that also count their thread's CPU time, in µs, as the
#: counter ``<name>.cpu_us`` (two ``thread_time`` reads a span)
CPU_SPANS = (
    "resident.issue_wave",      # the loop's thread: plan, route, issue
    "devindex.plan",            # inside it
    "query.results_work",       # a pool thread, under the core lock
)
_CPU_COUNTER = {name: f"{name}.cpu_us" for name in CPU_SPANS}
_thread_time = time.thread_time

_ids = itertools.count(1)

#: current span (None outside any SAMPLED trace)
_ctx: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "osse_trace_span", default=None)
#: current trace id — set even for UNSAMPLED traces so log prefixes
#: and debug=1 echoes work without paying for span bookkeeping
_tid_ctx: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "osse_trace_id", default=None)
#: the stage ledgers of the requests this thread works for: one on a
#: handler's thread, every rider's on a thread that runs a coalesced
#: batch, none elsewhere
_led_ctx: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "osse_stage_ledgers", default=())


class StageLedger:
    """One request's (stage, ms) rows, in the order they were written.
    Several threads append in turn (handler, batcher, pool, resident
    loop); ``list.append`` is all the synchronisation that takes."""

    __slots__ = ("rows",)

    def __init__(self):
        self.rows: list[tuple[str, float]] = []

    def add(self, name: str, ms: float) -> None:
        self.rows.append((name, ms))

    def stages(self) -> dict[str, float]:
        """stage -> ms, a stage met twice summed, first-met order."""
        out: dict[str, float] = {}
        for name, ms in list(self.rows):
            out[name] = out.get(name, 0.0) + ms
        return out

    def total_ms(self) -> float:
        return sum(ms for _, ms in list(self.rows))


class Span:
    """One timed node in a trace tree.

    Times are raw ``perf_counter`` seconds; offsets become milliseconds
    only at :meth:`to_dict`.  ``finish`` is idempotent — abandoned
    hedge attempts may finish long after the trace exported, and a
    still-unfinished span exports with ``abandoned: true`` and a
    duration running to the export instant.
    """

    __slots__ = ("trace_id", "span_id", "name", "host", "tags",
                 "children", "_grafts", "_t0", "_t1")

    def __init__(self, trace_id: str, name: str, host: str = "",
                 tags: dict | None = None):
        self.trace_id = trace_id
        self.span_id = f"{next(_ids):x}"
        self.name = name
        self.host = host
        self.tags = dict(tags) if tags else {}
        self.children: list[Span] = []
        #: remote subtrees (already-serialized dicts) from RPC replies
        self._grafts: list[dict] = []
        self._t0 = time.perf_counter()
        self._t1: float | None = None

    def tag(self, **kw) -> "Span":
        self.tags.update(kw)
        return self

    def finish(self) -> None:
        if self._t1 is None:
            self._t1 = time.perf_counter()

    def child(self, name: str, **tags) -> "Span":
        sp = Span(self.trace_id, name, host=self.host, tags=tags)
        self.children.append(sp)
        return sp

    def graft(self, subtree: dict) -> None:
        """Hang a remote host's exported subtree under this span."""
        if isinstance(subtree, dict):
            self._grafts.append(subtree)

    def record(self, name: str, t0: float, t1: float | None = None,
               **tags) -> "Span":
        """Attach an already-measured interval as a completed child —
        for call sites that timed themselves with ``perf_counter``."""
        sp = self.child(name, **tags)
        sp._t0 = t0
        sp._t1 = time.perf_counter() if t1 is None else t1
        return sp

    def to_dict(self, base_t0: float, end: float) -> dict:
        start_ms = (self._t0 - base_t0) * 1000.0
        t1 = self._t1
        d = {
            "id": self.span_id,
            "name": self.name,
            "host": self.host,
            "start_ms": round(start_ms, 3),
            "dur_ms": round(((end if t1 is None else t1) - self._t0)
                            * 1000.0, 3),
            "tags": dict(self.tags),
        }
        if t1 is None:
            d["tags"]["abandoned"] = True
        kids = [c.to_dict(base_t0, end) for c in self.children]
        # remote subtrees arrive with offsets relative to THEIR root;
        # rebase onto this (RPC) span's start so the waterfall lines up
        # without ever comparing two hosts' clocks
        kids.extend(_shift(g, start_ms) for g in self._grafts)
        if kids:
            d["children"] = kids
        return d


def _shift(node: dict, delta_ms: float) -> dict:
    out = dict(node)
    out["start_ms"] = round(node.get("start_ms", 0.0) + delta_ms, 3)
    if node.get("children"):
        out["children"] = [_shift(c, delta_ms) for c in node["children"]]
    return out


def span_count(node: dict) -> int:
    return 1 + sum(span_count(c) for c in node.get("children", ()))


# ---------------------------------------------------------------------------
# context helpers
# ---------------------------------------------------------------------------

def current_span() -> Span | None:
    return _ctx.get()


def current_trace_id() -> str | None:
    tid = _tid_ctx.get()
    if tid is not None:
        return tid
    sp = _ctx.get()
    return sp.trace_id if sp is not None else None


def begin(name: str, parent: Span | None = None, **tags) -> Span | None:
    """Open a child span WITHOUT making it current — for handing work
    to another thread.  Caller owns ``finish()``."""
    p = parent if parent is not None else _ctx.get()
    return None if p is None else p.child(name, **tags)


class attach:
    """Re-establish ``sp`` as the current span inside a worker thread
    (fresh threads start with an empty contextvars context)."""

    __slots__ = ("sp", "_tok", "_tok2")

    def __init__(self, sp: Span | None):
        self.sp = sp

    def __enter__(self) -> Span | None:
        if self.sp is None:
            self._tok = None
            return None
        self._tok = _ctx.set(self.sp)
        self._tok2 = _tid_ctx.set(self.sp.trace_id)
        return self.sp

    def __exit__(self, *exc) -> None:
        if self._tok is not None:
            _ctx.reset(self._tok)
            _tid_ctx.reset(self._tok2)


def current_ledgers() -> tuple:
    """The stage ledgers bound on this thread — what a hand-off to
    another thread carries along, beside the parent span."""
    return _led_ctx.get()


class bind_ledgers:
    """Bind the ledgers of the requests a thread works for: the
    handler binds its request's one, a pool or loop thread every
    rider's of the batch or wave it runs."""

    __slots__ = ("leds", "_tok")

    def __init__(self, leds):
        self.leds = tuple(leds)

    def __enter__(self) -> tuple:
        self._tok = _led_ctx.set(self.leds)
        return self.leds

    def __exit__(self, *exc) -> None:
        _led_ctx.reset(self._tok)


def _to_ledgers(name: str, ms: float, ledgers=None) -> None:
    if name in _STAGE_SET:
        for led in (_led_ctx.get() if ledgers is None else ledgers):
            led.add(name, ms)


#: ``jax.profiler.TraceAnnotation`` once jax is loaded (False where it
#: cannot be had); never imported from here: a process that has not
#: loaded jax has no profiler session to write into
_annotation = None


def _trace_annotation():
    """The annotation class while a profiler session runs, else None:
    with no session a span pays one ``is_enabled()`` call (0.07 us) and
    builds nothing."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            _annotation = jax.profiler.TraceAnnotation
            _annotation.is_enabled()
        except Exception:  # noqa: BLE001 — a span must never raise
            _annotation = False
    return _annotation if _annotation and _annotation.is_enabled() \
        else None


class span:
    """``with span("query.pack", npass=i):`` — child of the current
    span, no-op (yields None) outside a sampled trace."""

    __slots__ = ("name", "tags", "sp", "_tok")

    def __init__(self, name: str, **tags):
        self.name = name
        self.tags = tags

    def __enter__(self) -> Span | None:
        p = _ctx.get()
        if p is None:
            self.sp = None
            self._tok = None
            return None
        self.sp = p.child(self.name, **self.tags)
        self._tok = _ctx.set(self.sp)
        return self.sp

    def __exit__(self, *exc) -> None:
        if self.sp is not None:
            _ctx.reset(self._tok)
            self.sp.finish()


class timed_span:
    """A span that ALSO feeds ``g_stats.record_ms(name)`` — the query
    path uses this everywhere a ``g_stats.timed`` used to live, so the
    aggregate plane and the trace plane cannot drift apart. A stage
    (:data:`REQUEST_STAGES`) also lands in the bound stage ledgers,
    and while a ``jax.profiler`` session runs the interval is written
    onto its clock as a ``TraceAnnotation`` of the same name. ``t0`` /
    ``t1`` are the clock readings it made, for a neighbour that starts
    or ends on the same boundary (one clock a boundary). A span of
    :data:`CPU_SPANS` reads its thread's CPU clock inside ``t0`` ..
    ``t1`` and counts it as ``<name>.cpu_us``."""

    __slots__ = ("name", "_cm", "t0", "t1", "_ann", "_cpu0")

    def __init__(self, name: str, **tags):
        self.name = name
        self._cm = span(name, **tags)
        self.t1 = None

    def __enter__(self) -> Span | None:
        ann = _trace_annotation()
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self._cpu0 = _thread_time() if self.name in _CPU_COUNTER else None
        return self._cm.__enter__()

    def __exit__(self, *exc) -> None:
        cpu0 = self._cpu0
        if cpu0 is not None:
            cpu_us = int((_thread_time() - cpu0) * 1e6)
        self.t1 = time.perf_counter()
        self._cm.__exit__(*exc)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        # exemplar: when this interval ran under a SAMPLED trace, pin
        # its trace id to the histogram bucket it lands in — the
        # /admin/perf p99 cell links to the concrete /admin/traces
        # waterfall (Dapper's aggregate→trace bridge)
        sp = self._cm.sp
        ms = (self.t1 - self.t0) * 1000.0
        _to_ledgers(self.name, ms)
        exemplar = sp.trace_id if sp is not None else None
        if cpu0 is None:
            g_stats.record_ms(self.name, ms, exemplar=exemplar)
        else:
            g_stats.record_ms(self.name, ms, exemplar,
                              _CPU_COUNTER[self.name], cpu_us)


def record(name: str, t0: float, t1: float | None = None, *,
           parent: Span | None = None, ledgers=None,
           cpu0: float | None = None, **tags) -> None:
    """Attach an already-measured ``perf_counter`` interval to the
    current span AND to ``g_stats`` — like ``timed_span`` but for
    intervals the caller timed itself (device-time attribution after a
    block-until-ready). Feeding both planes here is what keeps ad-hoc
    ``perf_counter`` deltas off the query path (the ``adhoc-timing``
    lint rule). ``parent`` / ``ledgers`` name a request other than the
    thread's own: a wait measured by the thread that ended it (the
    batcher for a rider, the resident loop for a ticket). ``cpu0``,
    for a span of :data:`CPU_SPANS`, is this thread's ``thread_time``
    read at ``t0``: the CPU it has used since is counted."""
    ctr = _CPU_COUNTER.get(name) if cpu0 is not None else None
    cpu_us = int((_thread_time() - cpu0) * 1e6) if ctr is not None else 0
    end = time.perf_counter() if t1 is None else t1
    p = parent if parent is not None else _ctx.get()
    if p is not None:
        p.record(name, t0, end, **tags)
    ms = (end - t0) * 1000.0
    _to_ledgers(name, ms, ledgers)
    g_stats.record_ms(name, ms,
                      exemplar=p.trace_id if p is not None else None,
                      counter=ctr, n=cpu_us)


def finish_request(ledger: StageLedger, t0: float,
                   t1: float | None = None) -> None:
    """Close a served request's timeline: the container
    ``serve.request`` (request line read -> last byte written) and the
    stage ``serve.unaccounted``, what its ledger's stages leave of it.
    The request's trace closed (and, if slow, was exported) before the
    answer was written, so no exported line holds this stage: it is
    in ``g_stats`` and in the ledger the handler still holds."""
    end = time.perf_counter() if t1 is None else t1
    ms = (end - t0) * 1000.0
    rest = max(ms - ledger.total_ms(), 0.0)
    ledger.add("serve.unaccounted", rest)
    g_stats.record_ms("serve.request", ms)
    g_stats.record_ms("serve.unaccounted", rest)


def tag(**kw) -> None:
    """Merge tags into the current span, if any."""
    p = _ctx.get()
    if p is not None:
        p.tags.update(kw)


def header_for(sp: Span | None) -> str | None:
    return None if sp is None else f"{sp.trace_id}:{sp.span_id}"


def parse_header(value: str) -> tuple[str, str] | None:
    tid, sep, psid = (value or "").partition(":")
    if not sep or not tid:
        return None
    return tid, psid


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

class _LiveTrace:
    """Handle yielded by :meth:`Tracer.start` while the trace runs."""

    __slots__ = ("trace_id", "name", "sampled", "root", "ledger")

    def __init__(self, trace_id: str, name: str, sampled: bool,
                 root: Span, ledger: StageLedger | None = None):
        self.trace_id = trace_id
        self.name = name
        self.sampled = sampled
        self.root = root
        self.ledger = ledger if ledger is not None else StageLedger()

    def export(self) -> dict:
        end = (time.perf_counter() if self.root._t1 is None
               else self.root._t1)
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "sampled": self.sampled,
            "ts": time.time(),
            "dur_ms": round((end - self.root._t0) * 1000.0, 3),
            "stages": {k: round(v, 3)
                       for k, v in self.ledger.stages().items()},
            "root": self.root.to_dict(self.root._t0, end),
        }


class _Adopted:
    """Handle yielded by :meth:`Tracer.adopt` on the node side."""

    __slots__ = ("root",)

    def __init__(self, root: Span):
        self.root = root

    def export(self) -> dict:
        self.root.finish()
        return self.root.to_dict(self.root._t0, self.root._t1)


class _StartCM:
    def __init__(self, tracer: "Tracer", name: str, trace_id, sampled,
                 tags):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.sampled = sampled
        self.tags = tags
        self.trace: _LiveTrace | None = None

    def __enter__(self) -> _LiveTrace | None:
        tr = self.tracer
        if tr.sample_n <= 0:
            return None
        sampled = self.sampled
        if sampled is None:
            with tr._lock:
                tr._n += 1
                n = tr._n
            sampled = tr.sample_n == 1 or n % tr.sample_n == 0
        tid = self.trace_id or uuid.uuid4().hex[:16]
        root = Span(tid, self.name, host=tr.host, tags=self.tags)
        # the request's ledger: the one its handler bound, else (a
        # caller that is its own front door) one of the trace's own
        leds = _led_ctx.get()
        self.trace = _LiveTrace(tid, self.name, bool(sampled), root,
                                leds[0] if len(leds) == 1 else None)
        self._tok3 = None if len(leds) == 1 else \
            _led_ctx.set((self.trace.ledger,))
        self._tok = _ctx.set(root if sampled else None)
        self._tok2 = _tid_ctx.set(tid)
        g_stats.count("trace.started")
        if sampled:
            g_stats.count("trace.sampled")
        return self.trace

    def __exit__(self, *exc) -> None:
        t = self.trace
        if t is None:
            return
        _ctx.reset(self._tok)
        _tid_ctx.reset(self._tok2)
        if self._tok3 is not None:
            _led_ctx.reset(self._tok3)
        t.root.finish()
        self.tracer._finish(t)


class Tracer:
    """Process-wide trace collector: sampling decision, finished-trace
    ring, slow-query log.  One instance (:data:`g_tracer`); the serving
    layer configures it from the ``trace_sample`` / ``slow_query_ms``
    parms and points ``slowlog_path`` next to ``statsdb.jsonl``."""

    def __init__(self, sample_n: int = DEFAULT_SAMPLE_N,
                 slow_ms: float = DEFAULT_SLOW_MS):
        self.sample_n = sample_n
        self.slow_ms = slow_ms
        self.slowlog_path = None
        self.host = ""
        self.ring: deque[dict] = deque(maxlen=RING_KEEP)
        self._lock = threading.Lock()
        self._n = 0
        #: the second the slow-export bound is counting in, and its count
        self._clock = time.monotonic
        self._slow_t0 = 0.0
        self._slow_n = 0

    def configure(self, sample_n: int | None = None,
                  slow_ms: float | None = None,
                  slowlog_path=None, host: str | None = None) -> None:
        if sample_n is not None:
            self.sample_n = int(sample_n)
        if slow_ms is not None:
            self.slow_ms = float(slow_ms)
        if slowlog_path is not None:
            self.slowlog_path = slowlog_path
        if host is not None:
            self.host = host

    def start(self, name: str, trace_id: str | None = None,
              sampled: bool | None = None, **tags) -> _StartCM:
        """Open a root trace.  ``sampled=None`` → head-sampling coin
        flip; ``True`` forces a full trace (debug=1, tests)."""
        return _StartCM(self, name, trace_id, sampled, tags)

    def adopt(self, trace_id: str, parent_span_id: str, name: str,
              host: str = "") -> "attach":
        """Node-side: continue a remote trace under a local root span.
        Adopted traces never enter the local ring or slowlog — they
        ship back to the coordinator inside the RPC reply instead."""
        root = Span(trace_id, name, host=host or self.host)
        if parent_span_id:
            root.tags["parent"] = parent_span_id
        return _AdoptCM(root)

    def recent(self) -> list[dict]:
        return list(self.ring)

    def find(self, trace_id: str) -> dict | None:
        for t in reversed(self.ring):
            if t["trace_id"] == trace_id:
                return t
        return None

    def _finish(self, t: _LiveTrace) -> None:
        dur_ms = (t.root._t1 - t.root._t0) * 1000.0
        slow = self.slow_ms > 0 and dur_ms >= self.slow_ms
        keep_slow = slow and self._slow_admit()
        if slow:
            g_stats.count("trace.slow")
            if not keep_slow:
                g_stats.count("trace.slow_dropped")
        if not (t.sampled or keep_slow):
            return
        exported = t.export()
        exported["slow"] = slow
        self.ring.append(exported)
        if keep_slow:
            self._slowlog_append(exported)

    def _slow_admit(self) -> bool:
        """At most :data:`SLOWLOG_PER_S` slow exports in any one
        second: where every request is slow the log keeps a sample and
        a count, and the handler's thread pays neither the export nor
        the file."""
        now = self._clock()
        with self._lock:
            if now - self._slow_t0 >= 1.0:
                self._slow_t0, self._slow_n = now, 0
            self._slow_n += 1
            return self._slow_n <= SLOWLOG_PER_S

    def _slowlog_append(self, exported: dict) -> None:
        path = self.slowlog_path
        if path is None:
            return
        try:
            with self._lock:
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(exported) + "\n")
        except Exception as exc:  # noqa: BLE001 — never break serving
            g_stats.count("trace.slowlog_errors")
            log.debug("slowlog append failed: %s", exc)

    def slowlog_tail(self, n: int = 50) -> list[dict]:
        """Last ``n`` slowlog entries, skipping torn trailing lines
        (kill-9 mid-append leaves a partial JSON line)."""
        path = self.slowlog_path
        if path is None:
            return []
        try:
            lines = open(path, encoding="utf-8").read().splitlines()
        except OSError:
            return []
        out = []
        for line in lines[-n:]:
            try:
                out.append(json.loads(line))
            except Exception:  # noqa: BLE001
                continue
        return out


class _AdoptCM:
    """Context manager for :meth:`Tracer.adopt` — an :class:`attach`
    that also yields the adopted-trace handle."""

    __slots__ = ("adopted", "_att")

    def __init__(self, root: Span):
        self.adopted = _Adopted(root)
        self._att = attach(root)

    def __enter__(self) -> _Adopted:
        self._att.__enter__()
        return self.adopted

    def __exit__(self, *exc) -> None:
        self._att.__exit__(*exc)
        self.adopted.root.finish()


#: process-wide tracer
g_tracer = Tracer()
