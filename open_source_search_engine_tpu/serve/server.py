"""HTTP front end — search, cached pages, injection, admin.

Reference: ``HttpServer.cpp`` (nonblocking HTTP server) + ``Pages.cpp``
page table routing (``Pages.cpp:44,577``) + per-page handlers:
``PageResults.cpp`` (SERP in HTML/XML/JSON/CSV, ``PageResults.cpp:274``),
``PageGet.cpp`` (cached page w/ highlighting), ``PageInject.cpp``/
``PageAddUrl.cpp`` (content/url injection), ``PageStats``/``PageHosts``
(admin). Python stdlib threading server — the accept/parse plane is not
the bottleneck (queries are); a C++ front end can slot in front later
exactly like the reference's ``gb proxy`` mode.

Endpoints (reference query-string names kept: ``q``, ``n``, ``c``):

* ``GET /search?q=...&n=10&c=main&format=json|xml|html``
* ``GET /get?d=<docid>&q=...`` — cached page, query terms highlighted
* ``GET|POST /inject?u=<url>`` (body = content) — index a document
* ``GET /addurl?u=<url>`` — queue a url for the spider
* ``GET /admin/stats`` — counters; ``GET /admin/hosts`` — shard map
* ``GET /`` — minimal search form
"""

from __future__ import annotations

import html as html_mod
import json
import os
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

from ..index.collection import CollectionDb
from ..query import devcheck, engine, resident
from ..query.summary import highlight
from ..utils import chaos as chaos_mod
from ..utils import deadline as deadline_mod
from ..utils import threads
from ..utils.lockcheck import make_lock, make_rlock
from ..utils.log import get_logger
from ..utils.membudget import g_membudget
from ..utils import parms as parms_mod
from ..utils import priority as priority_mod
from ..utils import trace as trace_mod
from ..utils.parms import Conf
from ..utils.stats import g_stats
from ..utils.trace import g_tracer
from . import admission as admission_mod

log = get_logger("http")


class QueryBatcher:
    """Msg40 micro-batching: concurrent /search requests coalesce into
    ONE device dispatch (vmap over the query axis — SURVEY §7.8's
    throughput mode, which a one-lock-per-request server can never
    reach: its ceiling is 1/latency qps regardless of device speed).

    Requests enqueue and wait; one thread cuts same-parameter batches
    of ≤ MAX_B and hands each to a pool of ``2 * resident.DEPTH`` workers,
    each living a batch's whole life. Errors reach the batch's waiters."""

    MAX_B = 64
    WINDOW_S = 0.002  # brief collect window once a first query arrives
    #: bounded admission: an overload burst fails fast with QueueFull
    #: (the serve edge sheds stale-or-503) instead of growing host
    #: memory without bound
    MAX_QUEUE = 512
    #: per-waiter footprint estimate charged to the membudget "serve"
    #: label (query string + holder + span/deadline refs)
    QUEUE_ENTRY_COST = 4096

    def __init__(self, run_batch):
        #: run_batch((coll_name, topk, offset), [queries]) → [results]
        self._run_batch = run_batch
        self._cv = threading.Condition()
        #: (key, query, holder, parent span | None, deadline, tier,
        #: tenant, the rider's stage ledgers, when it was enqueued)
        self._queue: list[tuple] = []
        #: batches handed to the pool and not finished yet (waiting in
        #: its queue or on a worker); it only gates the collect window
        self._inflight = 0
        self._alive = True
        # a worker lives a batch's whole life: submit to the resident
        # loop, wait for the wave, build the results under the server's
        # core lock. The tails run one after another under that lock,
        # so with DEPTH waves in flight there have to be a wave's
        # worth of batches at the loop BEYOND those whose results are
        # being built: 2 * DEPTH out, or the loop's queue is empty at
        # every collect and host and device take turns (PERF.md, PR 31)
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(2 * resident.DEPTH,
                                        thread_name_prefix="query-pool")
        self._thread = threads.spawn("query-batcher", self._loop)

    @property
    def alive(self) -> bool:
        return self._alive

    def stop(self) -> None:
        """Kill the worker; fail queued waiters fast (they'd otherwise
        hang to their own timeout)."""
        with self._cv:
            self._alive = False
            for e in self._queue:
                e[2]["err"] = RuntimeError("query batcher stopped")
            self._queue.clear()
            self._gauge_locked()
            self._cv.notify_all()
        self._pool.shutdown(wait=False)

    def _gauge_locked(self) -> None:
        g_membudget.set_gauge(
            "serve", self, len(self._queue) * self.QUEUE_ENTRY_COST)

    def search(self, key: tuple, q: str, timeout: float = 60.0):
        holder: dict = {}
        # wait bounded by own timeout AND any bound query deadline —
        # whichever is sooner (the hedged-transport merge rule)
        dl = deadline_mod.current()
        deadline = deadline_mod.Deadline.after(timeout)
        if dl is not None and dl.at < deadline.at:
            deadline = dl
        # every return from the wait below, a notify's, a timeout's or
        # a spurious one; published once a request (the herd's count)
        wakes = 0
        try:
            with self._cv:
                if len(self._queue) >= self.MAX_QUEUE:
                    g_stats.count("admission.queue_full")
                    raise priority_mod.QueueFull(
                        "query batcher queue full")
                self._queue.append((key, q, holder,
                                    trace_mod.current_span(), dl,
                                    priority_mod.current_tier(),
                                    priority_mod.current_tenant(),
                                    trace_mod.current_ledgers(),
                                    time.perf_counter()))
                self._gauge_locked()
                self._cv.notify_all()
                while "res" not in holder and "err" not in holder:
                    left = deadline.remaining()
                    if left <= 0:
                        if dl is not None and dl.expired():
                            raise deadline_mod.DeadlineExceeded(
                                "query deadline exceeded in batcher")
                        raise TimeoutError("query batcher timeout")
                    self._cv.wait(timeout=left)
                    wakes += 1
        finally:
            if wakes:
                g_stats.count("batcher.rider_wake", wakes)
        if "err" in holder:
            raise holder["err"]
        # result set on the pool thread -> this rider's thread runs
        trace_mod.record("batcher.wake", holder["t_set"])
        return holder["res"]

    def _loop(self) -> None:
        while True:
            with self._cv:
                while self._alive and not self._queue:
                    self._cv.wait()
                if not self._alive:
                    return
                # fill-or-flush: a wave already in flight buys a
                # collect window (up to WINDOW_S hoping to fill a
                # same-key batch); an IDLE device launches immediately
                # with whatever is queued — queueing in front of idle
                # hardware is pure added latency
                if self._inflight > 0:
                    w = deadline_mod.Deadline.after(self.WINDOW_S)
                    while (self._alive and self._inflight > 0
                           and len(self._queue) < self.MAX_B):
                        left = w.remaining()
                        if left <= 0:
                            break
                        self._cv.wait(timeout=left)
                else:
                    g_stats.count("admission.wave.idle_flush")
                if not self._alive:
                    return
                if not self._queue:  # stop() drained it mid-window
                    continue
                key = self._queue[0][0]
                batch = [e for e in self._queue if e[0] == key][: self.MAX_B]
                for e in batch:
                    self._queue.remove(e)
                self._gauge_locked()
                self._inflight += 1
            # each rider waited from its own enqueue to this moment;
            # from here the batch waits as one (batcher.pool_wait)
            t_formed = time.perf_counter()
            for e in batch:
                trace_mod.record("batcher.queue_wait", e[8], t_formed,
                                 parent=e[3], ledgers=e[7])
            try:
                self._pool.submit(self._run_one, key, batch, t_formed)
            except RuntimeError as exc:  # pool shut down by stop()
                with self._cv:
                    self._inflight -= 1
                    for e in batch:
                        e[2]["err"] = exc
                    self._cv.notify_all()
                return

    def _run_one(self, key, batch, t_formed: float) -> None:
        try:
            self._run_one_inner(key, batch, t_formed)
        finally:
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()  # wake the fill-or-flush window

    def _run_one_inner(self, key, batch, t_formed: float) -> None:
        t_run = time.perf_counter()
        try:
            # worker thread = empty contextvars context; re-attach the
            # first traced waiter's span so the coalesced dispatch
            # lands in SOME trace, and mark the other waiters' traces
            # with a completed "coalesced" marker covering the interval
            parents = [e[3] for e in batch if len(e) > 3 and
                       e[3] is not None]
            # the coalesced dispatch runs under the LONGEST rider
            # budget (a short-deadline rider must not abandon every
            # other rider's wave; its own wait still times out)
            dls = [e[4] for e in batch
                   if len(e) > 4 and e[4] is not None]
            dl = max(dls, key=lambda d: d.at) if dls else None
            # ...and under the HIGHEST rider tier: a crawlbot rider
            # must not demote an interactive rider's coalesced wave
            tiers = [e[5] for e in batch
                     if len(e) > 5 and e[5] is not None]
            tier = (min(tiers, key=priority_mod.TIERS.index)
                    if tiers else None)
            # riders of one wave share a key => share a collection =>
            # share a tenant; carry the first one forward so the wave
            # bills (and sheds) against the right quota downstream
            tenants = [e[6] for e in batch
                       if len(e) > 6 and e[6] is not None]
            tenant = tenants[0] if tenants else None
            # every rider waited through the whole of each batch
            # stage: the pool and loop threads write them to all
            with trace_mod.attach(parents[0] if parents else None), \
                    trace_mod.bind_ledgers(
                        [led for e in batch for led in e[7]]), \
                    deadline_mod.bind(dl), \
                    priority_mod.bind_tier(tier), \
                    priority_mod.bind_tenant(tenant):
                trace_mod.record("batcher.pool_wait", t_formed, t_run,
                                 batch=len(batch))
                res = self._run_batch(key, [e[1] for e in batch])
            for p in parents[1:]:
                p.record("query.device_batch", t_run, coalesced=True,
                         batch=len(batch))
            t_set = time.perf_counter()
            with self._cv:
                for e, r in zip(batch, res):
                    e[2]["t_set"] = t_set
                    e[2]["res"] = r
                self._cv.notify_all()
        except Exception as exc:  # noqa: BLE001 — waiters must wake
            with self._cv:
                for e in batch:
                    e[2]["err"] = exc
                self._cv.notify_all()


class _FrontDoorHTTPServer(ThreadingHTTPServer):
    """The search server's socket server. A handler thread lives one
    connection; as it ends it adds its whole CPU time to the role
    ``front`` of the process's CPU account (``host.cpu_us.front``)."""

    def process_request_thread(self, request, client_address):
        try:
            super().process_request_thread(request, client_address)
        finally:
            threads.g_cpu.add_front()


def _xml_escape(s: str) -> str:
    return html_mod.escape(s, quote=True)


def render_results(res: engine.SearchResults, fmt: str,
                   trace_id: str | None = None) -> tuple[str, str]:
    """SERP rendering (PageResults.cpp HTML/XML/JSON/CSV).

    ``trace_id`` (``debug=1`` requests) is echoed in the body so a
    user-visible query can be looked up on ``/admin/traces``."""
    if fmt == "json":
        payload = {
            "query": res.query,
            "totalMatches": res.total_matches,
            "clustered": res.clustered,
            "suggestion": res.suggestion,
            "facets": {f: [[v, c] for v, c in pairs]
                       for f, pairs in (res.facets or {}).items()},
            "results": [
                {"docId": r.docid, "score": r.score, "url": r.url,
                 "title": r.title, "snippet": r.snippet, "site": r.site}
                for r in res.results
            ],
        }
        if trace_id:
            payload["traceId"] = trace_id
        return json.dumps(payload), "application/json"
    if fmt == "xml":
        rows = "".join(
            f"<result><docId>{r.docid}</docId>"
            f"<score>{r.score}</score>"
            f"<url>{_xml_escape(r.url)}</url>"
            f"<title>{_xml_escape(r.title)}</title>"
            f"<snippet>{_xml_escape(r.snippet)}</snippet></result>"
            for r in res.results)
        tid = (f"<traceId>{_xml_escape(trace_id)}</traceId>"
               if trace_id else "")
        return (f'<?xml version="1.0" encoding="UTF-8"?>'
                f"<response><query>{_xml_escape(res.query)}</query>"
                f"<totalMatches>{res.total_matches}</totalMatches>"
                f"{tid}{rows}</response>", "text/xml")
    if fmt == "csv":
        lines = ["docid,score,url,title"]
        for r in res.results:
            t = r.title.replace('"', '""')
            lines.append(f'{r.docid},{r.score},"{r.url}","{t}"')
        return "\n".join(lines), "text/csv"
    # html
    items = "".join(
        f'<li><a href="{html_mod.escape(r.url)}">'
        f"{html_mod.escape(r.title) or html_mod.escape(r.url)}</a>"
        f"<br><small>{html_mod.escape(r.snippet)}</small>"
        f"<br><code>{html_mod.escape(r.url)}</code> "
        f"<i>{r.score:.1f}</i></li>"
        for r in res.results)
    tid = (f'<p><small>trace <a href="/admin/traces?id='
           f'{html_mod.escape(trace_id)}">{html_mod.escape(trace_id)}'
           f"</a></small></p>" if trace_id else "")
    return (f"<html><head><title>{html_mod.escape(res.query)} - search"
            f"</title></head><body>"
            f'<form action="/search"><input name="q" '
            f'value="{html_mod.escape(res.query)}"><input type="submit" '
            f'value="search"></form>'
            f"<p>{res.total_matches} matches</p><ol>{items}</ol>{tid}"
            f"</body></html>", "text/html")


class SearchHTTPServer:
    """Owns the collections + (optionally) a sharded index and serves the
    reference's public endpoints."""

    def __init__(self, base_dir, host: str = "127.0.0.1", port: int = 8000,
                 sharded=None, spider=None, cluster=None,
                 conf: Conf | None = None):
        self.colldb = CollectionDb(base_dir)
        self.sharded = sharded  # ShardedCollection | None (in-process mesh)
        self.cluster = cluster  # ClusterClient | None (multi-process plane)
        self.spider = spider    # spider queue hook (addurl)
        self.host = host
        self.port = port
        self.conf = conf or Conf()
        gbconf = Path(base_dir) / "gb.conf"
        if conf is None and gbconf.exists():
            self.conf.load(gbconf)
        # guardrail wiring: the process memory budget tracks the live
        # max_mem parm (Conf::m_maxMem → g_mem), and the checkify parm
        # arms the device-plane harness (OSSE_CHECKIFY equivalent)
        g_membudget.set_limit(self.conf.max_mem)
        if self.conf.checkify:
            devcheck.set_enabled(True)
        # trace plane wiring: sampling + slow-query threshold from the
        # parms, slowlog next to statsdb (process-global tracer — the
        # last server constructed in a process owns the slowlog path)
        g_tracer.configure(sample_n=self.conf.trace_sample,
                           slow_ms=self.conf.slow_query_ms,
                           slowlog_path=Path(base_dir) / "slowlog.jsonl",
                           host=f"{host}:{port}")
        self.conf.on_update(self._on_guardrail_parm)
        self.stats = {"queries": 0, "injects": 0, "addurls": 0,
                      "gets": 0, "errors": 0, "auth_denied": 0}
        self._httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        # the Rdb/MemTable/caches are single-writer structures (the
        # reference's whole core is single-threaded event-driven,
        # SURVEY §1); the threaded accept plane serializes at this lock
        self._lock = make_rlock("server.core")
        #: /search micro-batching (flat device path only; the sharded
        #: and cluster planes batch at their own layers)
        self._batcher = QueryBatcher(self._run_device_batch)
        #: admission plane: bounded, tiered gate in front of the
        #: dispatch planes — sheds stale-or-503 before the membudget
        #: ever has to refuse real work (serve/admission.py)
        self.admission = admission_mod.AdmissionGate()
        #: tenant plane: the residency manager owns every collection's
        #: (DeviceIndex, ResidentLoop) lifecycle; its hot-set count
        #: rides the tenant_hot parm, its byte bound the membudget
        #: "device" label cap (device_budget parm, 0 = uncapped)
        from .tenancy import g_residency
        g_residency.configure(
            max_resident=int(getattr(self.conf, "tenant_hot", 0)))
        g_residency.attach(g_membudget)
        if int(getattr(self.conf, "device_budget", 0)) > 0:
            g_membudget.set_label_cap(
                "device", int(self.conf.device_budget))
        #: statsdb persistence (reference Statsdb: an on-disk ring of
        #: timestamped metric samples behind PagePerf graphs)
        self._statsdb_path = Path(base_dir) / "statsdb.jsonl"
        self._sampler: threading.Thread | None = None
        self._stop_sampling = threading.Event()
        #: crawlbot job registry (lazy; PageCrawlBot.cpp role) and an
        #: injectable fetcher factory for tests
        self._crawlbot = None
        self.crawl_fetcher_factory = None
        #: AutoBan (AutoBan.cpp): per-IP query rate limiting. hits =
        #: ip → recent request timestamps; banned = ip → ban expiry
        self._ab_lock = make_lock("server.autoban")
        self._ab_hits: dict[str, list[float]] = {}
        self._ab_banned: dict[str, float] = {}
        #: niceness gate: background requests yield to interactive
        from ..utils.nice import NicenessGate
        self.nice_gate = NicenessGate()
        #: Msg17/Msg40Cache: rendered result pages on the cache plane.
        #: Generation-keyed per request via _result_gen — local index
        #: version single-node, the shard/cluster generation vector on
        #: the distributed planes (so a remote write invalidates the
        #: SERP too, closing the stale-after-delete window the old
        #: fixed-TTL cache had)
        from ..cache import g_cacheplane
        self._result_cache = g_cacheplane.register(
            "server.results", ttl_s=30.0, max_entries=2048,
            desc="rendered result pages (Msg17/Msg40Cache role)")
        #: per-user admin accounts (Users.cpp / users.txt)
        from ..utils.users import Users
        self.users = Users(base_dir)

    def _on_guardrail_parm(self, name: str, value) -> None:
        """Live parm updates feeding the guardrail planes (the 0x3f
        broadcast applies here too via attach_conf → set)."""
        if name == "max_mem":
            g_membudget.set_limit(int(value))
        elif name == "checkify":
            # False reverts to the env default rather than forcing off,
            # so OSSE_CHECKIFY=1 test runs survive a parm sync
            devcheck.set_enabled(True if value else None)
        elif name == "trace_sample":
            g_tracer.configure(sample_n=int(value))
        elif name == "slow_query_ms":
            g_tracer.configure(slow_ms=float(value))
        elif name == "tenant_hot":
            from .tenancy import g_residency
            g_residency.configure(max_resident=int(value))
        elif name == "device_budget":
            g_membudget.set_label_cap("device", int(value))

    BAN_COOLDOWN_S = 60.0

    def _autobanned(self, ip: str, limit_qps: int) -> bool:
        """Sliding 1-second window per client IP; exceeding the limit
        bans the IP for BAN_COOLDOWN_S (reference AutoBan bans abusive
        query sources and returns an error page)."""
        if not limit_qps or not ip:
            return False
        now = time.monotonic()
        with self._ab_lock:
            until = self._ab_banned.get(ip, 0.0)
            if until > now:
                return True
            hits = self._ab_hits.setdefault(ip, [])
            hits.append(now)
            del hits[: max(0, len(hits) - 4 * limit_qps)]
            recent = [t for t in hits if t > now - 1.0]
            if len(recent) > limit_qps:
                self._ab_banned[ip] = now + self.BAN_COOLDOWN_S
                # the cooldown IS the penalty: drop the window so the
                # first post-ban request is judged on fresh traffic —
                # stale pre-ban hits must not re-ban it on sight (a
                # banned client could otherwise never requalify)
                self._ab_hits.pop(ip, None)
                if len(self._ab_banned) > 4096:
                    self._ab_banned = {
                        k: v for k, v in self._ab_banned.items()
                        if v > now}
                log.warning("autoban: %s exceeded %d qps", ip,
                            limit_qps)
                return True
            if len(self._ab_hits) > 8192:  # bound the tracking table
                self._ab_hits = {ip: hits}
        return False

    def _run_device_batch(self, key: tuple, queries: list[str]):
        cname, topk, offset = key
        # nothing on the way to the resident loop's queue may take the
        # core lock (the registry has a lock of its own, as for the
        # front door's lookup): a submit that queues behind another
        # batch's results tail leaves the loop with nothing to issue
        # while it collects. The core lock covers, via results_lock,
        # the host post-processing alone, which reads the single-writer
        # Rdb/titledb structures.
        coll = self.colldb.get(cname)
        return engine.search_device_batch(
            coll, queries, topk=topk, offset=offset,
            resident=True, results_lock=self._lock)

    def _authorized(self, query: dict,
                    min_role: str = "admin") -> bool:
        """Auth gate for /admin and mutating endpoints: the master
        password (Conf::m_masterPwds) OR a per-user credential from
        the users table (Users.cpp — ``user=``/``upwd=`` params) at
        the required role. Empty master password AND empty user table
        = open instance."""
        pwd = self.conf.master_password
        has_users = bool(self.users.names())
        if not pwd and not has_users:
            return True
        if pwd and query.get("pwd", "") == pwd:
            return True
        u = query.get("user", "")
        if u and self.users.check(u, query.get("upwd", ""),
                                  min_role=min_role):
            return True
        return False

    # --- request handling -------------------------------------------------

    def handle(self, method: str, path: str, query: dict,
               body: bytes, client_ip: str = "",
               niceness: int = 0,
               tier: str | None = None,
               tenant: str | None = None) -> tuple[int, str, str]:
        """Route one request → (status, payload, content_type).
        The Pages.cpp s_pages[] table, as a method. Background
        (niceness-1) requests yield to in-flight interactive ones
        (UdpProtocol.h niceness bit). ``tier``/``tenant`` are
        propagated X-OSSE-Priority / X-OSSE-Tenant verdicts, if the
        caller carried them."""
        # drop any extra response headers a previous request left on
        # this thread's context (direct handle() callers never pop)
        admission_mod.pop_response_headers()
        self.nice_gate.enter(niceness)
        try:
            return self._handle_inner(method, path, query, body,
                                      client_ip, niceness=niceness,
                                      header_tier=tier,
                                      header_tenant=tenant)
        finally:
            self.nice_gate.exit(niceness)

    def _handle_inner(self, method: str, path: str, query: dict,
                      body: bytes, client_ip: str = "",
                      niceness: int = 0,
                      header_tier: str | None = None,
                      header_tenant: str | None = None
                      ) -> tuple[int, str, str]:
        try:
            if path == "/":
                return 200, self._page_root(), "text/html"
            if path == "/search":
                # autoban runs BEFORE any collection lookup, and read
                # paths never create collections — unauthenticated
                # requests with arbitrary c= names must not mint
                # directory trees on disk (nor bypass the rate limit)
                coll = self._coll_read(query)
                # unknown-collection requests still get the COLL-scope
                # default limit — 404ing must not bypass the rate gate
                limit = int(coll.conf.autoban_qps) if coll is not None \
                    else int(parms_mod.parm("autoban_qps").default)
                if self._autobanned(client_ip, limit):
                    g_stats.count("autoban.rejected")
                    return 429, json.dumps(
                        {"error": "query rate limit (autoban)"}), \
                        "application/json"
                if coll is None and self.sharded is None \
                        and self.cluster is None:
                    return 404, json.dumps(
                        {"error": "no such collection"}), \
                        "application/json"
                # front-door classification (admission plane): explicit
                # tier= param > propagated header > niceness bit, else
                # interactive — bound so scatter legs inherit it
                tier = priority_mod.classify(query, niceness=niceness,
                                             header_tier=header_tier)
                g_stats.count(f"admission.tier.{tier}")
                # the billing tenant IS the collection (the crawlbot
                # customer); a propagated header keeps a scatter leg
                # on its coordinator's quota ledger
                tenant = header_tenant or query.get("c", "main")
                # NOT under the global lock: the micro-batcher would
                # deadlock (its worker takes the lock), and holding it
                # per-request caps the plane at 1/latency qps
                with priority_mod.bind_tier(tier), \
                        priority_mod.bind_tenant(tenant):
                    return self._page_search(query, tier=tier,
                                             tenant=tenant)
            with self._lock:
                return self._route(method, path, query, body)
        except Exception as e:  # noqa: BLE001 — server must not die
            self.stats["errors"] += 1
            log.warning("error handling %s: %s", path, e)
            return 500, json.dumps({"error": str(e)}), "application/json"

    def _route(self, method: str, path: str, query: dict,
               body: bytes) -> tuple[int, str, str]:
        if path == "/get":
            return self._page_get(query)
        if path == "/crawlbot":
            # REST bulk-crawl API (PageCrawlBot.cpp) — admin-gated
            # like every index-mutating endpoint
            if not self._authorized(query):
                self.stats["auth_denied"] += 1
                return 401, json.dumps(
                    {"error": "bad or missing pwd"}), "application/json"
            return self._page_crawlbot(query)
        if path in ("/inject", "/addurl", "/delete"):
            # index-mutating endpoints are admin-gated once a master
            # password is set (the reference gates injection behind the
            # admin password, PageInject/Pages auth)
            if not self._authorized(query):
                self.stats["auth_denied"] += 1
                return 401, json.dumps(
                    {"error": "bad or missing pwd"}), "application/json"
            if path == "/inject":
                return self._page_inject(query, body)
            if path == "/delete":
                return self._page_delete(query)
            return self._page_addurl(query)
        if path == "/metrics":
            # Prometheus-style exposition for EXTERNAL scrapers — like
            # /search it is unauthenticated read-only plumbing, outside
            # the /admin password gate
            return 200, self._metrics_text(), "text/plain"
        if path.startswith("/admin") and not self._authorized(query):
            self.stats["auth_denied"] += 1
            return 401, json.dumps({"error": "bad or missing pwd"}), \
                "application/json"
        if path in ("/admin", "/admin/"):
            return 200, self._page_admin_index(query), "text/html"
        if path == "/admin/profiler":
            return self._page_profiler(query)
        if path == "/admin/graph":
            return 200, self._page_graph(), "image/svg+xml"
        if path == "/admin/stats":
            stats = dict(self.stats)
            # corrupt-run quarantine state (Msg5 error correction)
            q: dict[str, list] = {}
            if self.sharded is not None:
                for s, row in enumerate(self.sharded.grid):
                    for r, coll in enumerate(row):
                        for rn, rdb in coll.rdbs().items():
                            if rdb.quarantined:
                                q[f"shard{s}_r{r}:{rn}"] = rdb.quarantined
            elif self.colldb is not None:
                q = {f"{cn}:{rn}": rdb.quarantined
                     for cn in self.colldb.names()
                     for rn, rdb in self.colldb.get(cn).rdbs().items()
                     if rdb.quarantined}
            if q:
                stats["quarantined_runs"] = q
            return 200, json.dumps(stats), "application/json"
        if path == "/admin/hosts":
            return 200, self._page_hosts(), "application/json"
        if path == "/admin/perf":
            return self._page_perf(query)
        if path == "/admin/mem":
            return self._page_mem(query)
        if path == "/admin/transport":
            return self._page_transport(query)
        if path == "/admin/cache":
            return self._page_cache(query)
        if path == "/admin/traces":
            return self._page_traces(query)
        if path == "/admin/parms":
            return self._page_parms(query)
        if path == "/admin/jit":
            return self._page_jit(query)
        if path == "/admin/hbm":
            return self._page_hbm(query)
        if path == "/admin/device":
            return self._page_device(query)
        if path == "/admin/admission":
            return self._page_admission(query)
        if path == "/admin/tenants":
            return self._page_tenants(query)
        return 404, json.dumps({"error": "no such page"}), \
            "application/json"

    def _coll(self, query: dict):
        return self.colldb.get(query.get("c", "main"))

    def _coll_read(self, query: dict):
        """Read-path collection lookup: NEVER creates on-disk state for
        arbitrary ``c=`` names — except the default collection, which
        stays lazily creatable (a fresh instance must answer
        ``/search?q=x`` with zero results, not 404). Returns None for
        unknown collections."""
        name = query.get("c", "main")
        try:
            return self.colldb.get(name, create=(name == "main"))
        except KeyError:
            return None

    def _page_root(self) -> str:
        return ('<html><body><form action="/search">'
                '<input name="q"><input type="submit" value="search">'
                "</form></body></html>")

    def _page_search(self, query: dict,
                     tier: str = "interactive",
                     tenant: str | None = None) -> tuple[int, str, str]:
        q = query.get("q", "")
        if not q:
            return 400, json.dumps({"error": "missing q"}), \
                "application/json"
        # debug=1: force-sample this query's trace and echo the trace
        # id in the body so the waterfall can be pulled up by id
        debug = query.get("debug", "") not in ("", "0")
        with g_tracer.start("search", sampled=True if debug else None,
                            q=q, tier=tier) as tr:
            # the whole-request latency histogram (cache hits and
            # degraded answers included) — what a single-node SLO
            # reads; the per-tier twin is what the overload harness
            # asserts on (interactive p99 bounded while crawlbot sheds)
            with trace_mod.timed_span("serve.search"), \
                    trace_mod.timed_span(f"serve.search.{tier}"):
                out = self._page_search_traced(query, q, debug, tr,
                                               tier=tier,
                                               tenant=tenant)
        return out

    def _query_deadline(self, query: dict):
        """The per-query budget: ``deadline_ms=`` on the request, else
        the ``OSSE_DEADLINE_MS`` env default; absent/0 = unbudgeted."""
        raw = query.get("deadline_ms", "") \
            or os.environ.get("OSSE_DEADLINE_MS", "")
        try:
            ms = float(raw)
        except (TypeError, ValueError):
            return None
        if ms <= 0:
            return None
        return deadline_mod.Deadline.after(ms / 1000.0)

    def _page_search_traced(self, query: dict, q: str, debug: bool,
                            tr, tier: str = "interactive",
                            tenant: str | None = None
                            ) -> tuple[int, str, str]:
        n = min(int(query.get("n", 10)), 100)
        # deep paging: first result number (reference PageResults s=),
        # bounded so a hostile s can't force a corpus-sized top-k
        s = min(max(int(query.get("s", 0)), 0), 100000)
        fmt = query.get("format", "json")
        self.stats["queries"] += 1
        cname = query.get("c", "main")
        rc_coll = self._coll_read(query)
        ttl = float(getattr(rc_coll.conf, "result_cache_ttl", 0)
                    if rc_coll is not None else 0)
        swr = float(getattr(rc_coll.conf, "result_cache_swr", 0)
                    if rc_coll is not None else 0)
        ckey = gen = None
        # debug requests bypass the result cache both ways: a cached
        # body would echo a STALE trace id, and a debug body must not
        # poison the cache for ordinary requests
        if ttl > 0 and not debug:
            gen = self._result_gen(rc_coll)
            ckey = (cname, q, n, s, fmt)
        dl = self._query_deadline(query)
        # a fresh cache hit bypasses the admission gate entirely —
        # serving from memory costs nothing the gate protects, and
        # under overload the hot head of the Zipf mix must keep
        # answering (the reference's Msg17 hits skip Msg39 queueing)
        if ckey is not None:
            hit, page = self._result_cache.lookup(ckey, gen=gen)
            if hit:
                self.stats["result_cache_hits"] = \
                    self.stats.get("result_cache_hits", 0) + 1
                trace_mod.tag(result_cache="hit")
                return page
        try:
            token = self.admission.admit(tier, deadline=dl,
                                         tenant=tenant)
        except admission_mod.Shed as shed:
            return self._shed_response(shed, ckey, gen)
        try:
            with token, deadline_mod.bind(dl):
                out = self._search_cached(query, q, n, s, fmt, rc_coll,
                                          debug, tr, ckey, gen, ttl,
                                          swr)
            deadline_mod.note_met(dl)
            return out
        except priority_mod.QueueFull:
            # a bounded dispatch queue (batcher/resident) refused the
            # enqueue past the gate — same shed ladder, same accounting
            return self._shed_response(
                admission_mod.Shed("queue_full"), ckey, gen)
        except deadline_mod.DeadlineExceeded:
            # budget burned downstream: the cache plane's just-expired
            # answer (same generation — a write still invalidates)
            # beats a refusal; it goes out marked degraded
            if ckey is not None:
                hit, page = self._result_cache.lookup_stale(ckey,
                                                            gen=gen)
                if hit:
                    g_stats.count("deadline.stale_served")
                    trace_mod.tag(deadline="expired",
                                  results="degraded")
                    self.stats["deadline_stale"] = \
                        self.stats.get("deadline_stale", 0) + 1
                    return page
            g_stats.count("deadline.refused")
            return 504, json.dumps({"error": "deadline exceeded"}), \
                "application/json"

    def _shed_response(self, shed: admission_mod.Shed, ckey, gen
                       ) -> tuple[int, str, str]:
        """The shed ladder, cheapest first: the cache plane's
        same-generation SWR-stale answer marked degraded, else 503 +
        Retry-After. Every shed is counted — the load harness asserts
        none are silently lost."""
        if ckey is not None:
            hit, page = self._result_cache.lookup_stale(ckey, gen=gen)
            if hit:
                g_stats.count("admission.shed.stale")
                trace_mod.tag(admission=shed.reason,
                              results="degraded")
                self.stats["admission_stale"] = \
                    self.stats.get("admission_stale", 0) + 1
                return page
        g_stats.count("admission.shed.refused")
        trace_mod.tag(admission=shed.reason, results="refused")
        self.stats["admission_refused"] = \
            self.stats.get("admission_refused", 0) + 1
        retry = max(1, int(round(shed.retry_after_s)))
        admission_mod.set_response_header("Retry-After", str(retry))
        return 503, json.dumps(
            {"error": f"overloaded ({shed.reason})",
             "retryAfter": retry}), "application/json"

    def _search_cached(self, query: dict, q: str, n: int, s: int,
                       fmt: str, rc_coll, debug: bool, tr, ckey, gen,
                       ttl: float, swr: float) -> tuple[int, str, str]:
        # Msg17/Msg40Cache result cache: identical pages within the TTL
        # serve from memory. Single-node, the LOCAL index version in
        # the key invalidates instantly on mutation; the distributed
        # planes (cluster/sharded) mutate on remote nodes this frontend
        # can't version-watch, so there staleness is bounded by the TTL
        # alone (the reference's Msg17 accepts the same bound).
        deg: dict = {}
        if ckey is not None:
            hit, page = self._result_cache.lookup(ckey, gen=gen)
            if hit:
                self.stats["result_cache_hits"] = \
                    self.stats.get("result_cache_hits", 0) + 1
                trace_mod.tag(result_cache="hit")
                return page
            if swr > 0:
                # stale-while-revalidate for hot SERPs: serve the
                # just-expired page and refresh in the background —
                # never across a generation move (get_or_compute
                # enforces that), so a write still invalidates
                # instantly
                page, status = self._result_cache.get_or_compute(
                    ckey,
                    lambda: self._render_search(query, q, n, s, fmt,
                                                rc_coll, debug, tr,
                                                degraded_out=deg),
                    ttl_s=ttl, gen=gen, swr_s=swr)
                if status in ("hit", "stale", "join"):
                    self.stats["result_cache_hits"] = \
                        self.stats.get("result_cache_hits", 0) + 1
                    trace_mod.tag(result_cache=status)
                if deg.get("degraded"):
                    # a degraded partial must not serve for a TTL as if
                    # it were the full answer
                    self._result_cache.invalidate(ckey)
                return page
        page = self._render_search(query, q, n, s, fmt, rc_coll,
                                   debug, tr, degraded_out=deg)
        if ckey is not None and not deg.get("degraded"):
            self._result_cache.put(ckey, page, ttl_s=ttl, gen=gen)
        return page

    def _result_gen(self, rc_coll) -> tuple:
        """The result cache's generation for one request: whatever
        version vector a write to ANY backing index would move —
        local posdb single-node, every shard's generation on the
        distributed planes (the write-path invalidation contract)."""
        if self.cluster is not None:
            return ("cluster",) + self.cluster.gen_vector()
        if self.sharded is not None:
            return ("sharded",) + tuple(
                coll.posdb.version
                for row in self.sharded.grid for coll in row)
        return ("flat",
                rc_coll.posdb.version if rc_coll is not None else 0)

    def _render_search(self, query: dict, q: str, n: int, s: int,
                       fmt: str, rc_coll, debug: bool, tr,
                       degraded_out: dict | None = None
                       ) -> tuple[int, str, str]:
        if self.cluster is not None:
            # conf is only consulted for PQR factors — never create a
            # local collection just to read it (rc_coll above already
            # did the read-only lookup)
            res = self.cluster.search(
                q, topk=n, offset=s,
                conf=rc_coll.conf if rc_coll else None)
        elif self.sharded is not None:
            if self.conf.serve_mesh:
                # mesh-resident serving: the ticket wave dispatches ONE
                # shard_map program across all chips (in-jit Msg3a merge
                # + site dedup); the ResidentLoop serializes device
                # work, so the lock guards only host post-processing
                res = engine.get_mesh_resident(self.sharded).serve(
                    q, topk=n, offset=s, results_lock=self._lock)
            else:
                from ..parallel import sharded_search
                with self._lock:
                    res = sharded_search(self.sharded, q, topk=n,
                                         offset=s)
        elif self.conf.serve_device:
            # resident-index path through the micro-batcher: concurrent
            # requests share one vmapped dispatch
            try:
                res = self._batcher.search(
                    (query.get("c", "main"), n, s), q)
            except deadline_mod.DeadlineExceeded:
                raise  # serve edge owns expiry (stale-or-504)
            except priority_mod.QueueFull:
                # overload: the host-fallback path below would ADD load
                # exactly when the plane is saturated — shed instead
                raise
            except Exception as e:  # noqa: BLE001 — degrade, don't 500
                # counted: an answer that came from the host while
                # serve_device is on hides the device (chip_smoke.py
                # requires this counter to stay 0)
                g_stats.count("serve.device_fallback")
                log.warning("device search failed (%r); host fallback",
                            e)
                with self._lock:
                    res = engine.search(self._coll(query), q, topk=n,
                                        offset=s)
        else:
            with self._lock:
                res = engine.search(self._coll(query), q, topk=n,
                                    offset=s)
        if getattr(res, "degraded", False):
            # a scatter leg timed out / failed past the hedge: partial
            # answer, stamped so the caller skips the result cache
            if degraded_out is not None:
                degraded_out["degraded"] = True
            self.stats["degraded"] = self.stats.get("degraded", 0) + 1
            trace_mod.tag(results="degraded")
        with trace_mod.timed_span("serve.render"):
            payload, ctype = render_results(
                res, fmt,
                trace_id=tr.trace_id if (debug and tr is not None)
                else None)
        return 200, payload, ctype

    def _page_get(self, query: dict) -> tuple[int, str, str]:
        """Cached page w/ optional highlight (PageGet.cpp)."""
        from ..build import docproc
        docid = int(query.get("d", "0"))
        self.stats["gets"] += 1
        if self.cluster is not None:
            rec = self.cluster.get_document(docid)
        elif self.sharded is not None:
            rec = self.sharded.get_document(docid)
        else:
            coll = self._coll_read(query)  # read path: never mint colls
            rec = docproc.get_document(coll, docid=docid) \
                if coll is not None else None
        if rec is None:
            return 404, json.dumps({"error": "not found"}), \
                "application/json"
        content = rec.get("content", rec.get("text", ""))
        terms = [w for w in query.get("q", "").split() if w]
        if terms:
            content = highlight(content, terms,
                                pre='<span style="background:yellow">',
                                post="</span>")
        return 200, content, "text/html"

    def _page_inject(self, query: dict, body: bytes) -> tuple[int, str, str]:
        """Direct content injection (PageInject.cpp / msgtype 0x07)."""
        from ..build import docproc
        url = query.get("u") or query.get("url")
        if not url:
            return 400, json.dumps({"error": "missing u"}), \
                "application/json"
        content = body.decode("utf-8", "replace") if body else \
            query.get("content", "")
        self.stats["injects"] += 1
        if self.cluster is not None:
            docid = self.cluster.index_document(url, content)
            return 200, json.dumps({"docId": int(docid)}), \
                "application/json"
        if self.sharded is not None:
            ml = self.sharded.index_document(url, content)
        else:
            ml = docproc.index_document(self._coll(query), url, content)
        if ml is None:  # tagdb manualban (EDOCBANNED)
            return 403, json.dumps({"error": "banned by tagdb"}), \
                "application/json"
        return 200, json.dumps({"docId": ml.docid,
                                "numKeys": len(ml.posdb_keys)}), \
            "application/json"

    def _page_delete(self, query: dict) -> tuple[int, str, str]:
        """Remove a url from the index (PageInject's delete form /
        msgtype 0x07 with delete=1). The write bumps the backing
        index's generation, which invalidates every dependent cache
        entry — the inject→delete regression test drives this route."""
        from ..build import docproc
        url = query.get("u") or query.get("url")
        if not url:
            return 400, json.dumps({"error": "missing u"}), \
                "application/json"
        self.stats["deletes"] = self.stats.get("deletes", 0) + 1
        if self.cluster is not None:
            self.cluster.remove_document(url)
            return 200, json.dumps({"deleted": url}), \
                "application/json"
        if self.sharded is not None:
            ok = self.sharded.remove_document(url)
        else:
            ok = docproc.remove_document(self._coll(query), url)
        if not ok:
            return 404, json.dumps({"error": "not found"}), \
                "application/json"
        return 200, json.dumps({"deleted": url}), "application/json"

    def _page_addurl(self, query: dict) -> tuple[int, str, str]:
        """Queue a url for spidering (PageAddUrl.cpp)."""
        url = query.get("u") or query.get("url")
        if not url:
            return 400, json.dumps({"error": "missing u"}), \
                "application/json"
        self.stats["addurls"] += 1
        if self.spider is None:
            return 503, json.dumps({"error": "spider not running"}), \
                "application/json"
        self.spider.add_url(url)
        return 200, json.dumps({"queued": url}), "application/json"

    def _page_crawlbot(self, query: dict) -> tuple[int, str, str]:
        """REST crawl jobs (PageCrawlBot.cpp): create/status/pause/
        resume/delete; corpora search via /search?c=crawl_<name>."""
        from .crawlbot import CrawlBot
        # two concurrent first requests must not each build a CrawlBot
        # (the loser's job state would be dropped on publish)
        with self._lock:
            if self._crawlbot is None:
                self._crawlbot = CrawlBot(self.colldb,
                                          fetcher_factory=
                                          self.crawl_fetcher_factory)
            bot = self._crawlbot
        name = query.get("name", "")
        if not name:
            return 200, json.dumps({"jobs": bot.list_jobs()}),                 "application/json"
        action = query.get("action", "")
        if action in ("pause", "resume"):
            job = bot.get(name)
            if job is None:
                return 404, json.dumps({"error": "no such job"}),                     "application/json"
            job.paused = action == "pause"
            return 200, json.dumps(job.status()), "application/json"
        if action == "delete":
            ok = bot.delete(name)
            return (200 if ok else 404), json.dumps({"deleted": ok}),                 "application/json"
        seeds = [u for u in (query.get("seeds", "") or "").replace(
            ",", " ").split() if u]
        if seeds:
            try:
                job = bot.create(
                    name, seeds,
                    max_pages=int(query.get("maxpages", 100)),
                    max_hops=int(query.get("maxhops", 3)),
                    same_host_only=query.get("spanhosts", "0")
                    not in ("1", "true"))
            except ValueError as e:
                return 409, json.dumps({"error": str(e)}),                     "application/json"
            return 200, json.dumps(job.status()), "application/json"
        job = bot.get(name)
        if job is None:
            return 404, json.dumps({"error": "no such job"}),                 "application/json"
        return 200, json.dumps(job.status()), "application/json"

    def _page_parms(self, query: dict) -> tuple[int, str, str]:
        """Parameter view + live update via cgi names — the Parms URL api
        (``&maxmem=...``); updates fire the conf's on_update listeners
        (the 0x3f cluster-broadcast hook)."""
        from ..utils import parms as parms_mod
        coll = self._coll(query)
        updated = {}
        for cgi, value in query.items():
            if cgi in ("c",):
                continue
            for target in (coll.conf,):
                try:
                    target.set_from_cgi(cgi, value)
                    updated[cgi] = value
                    break
                except KeyError:
                    continue
        table = [{
            "name": p.name, "cgi": p.cgi, "type": p.type.__name__,
            "default": p.default, "scope": p.scope, "desc": p.desc,
        } for p in parms_mod.parm_table()]
        return 200, json.dumps({
            "updated": updated,
            "coll": coll.conf.to_dict(),
            "table": table,
        }), "application/json"

    # --- admin HTML (Pages.cpp admin page set) ---------------------------

    def _page_admin_index(self, query: dict) -> str:
        pwd = query.get("pwd", "")
        sfx = f"?pwd={urllib.parse.quote(pwd)}" if pwd else ""
        links = "".join(
            f'<li><a href="/admin/{p}{sfx}">{p}</a></li>'
            for p in ("stats", "hosts", "perf", "mem", "transport",
                      "cache", "traces", "parms", "jit", "hbm",
                      "device", "admission", "tenants", "profiler",
                      "graph")) + '<li><a href="/metrics">metrics</a></li>'
        rows = "".join(f"<tr><td>{k}</td><td>{v}</td></tr>"
                       for k, v in self.stats.items())
        colls = ", ".join(self.colldb.names())
        return (f"<html><head><title>gb admin</title></head><body>"
                f"<h1>admin</h1><p>collections: {colls}</p>"
                f"<ul>{links}</ul><table border=1>{rows}</table>"
                f"</body></html>")

    def _page_admission(self, query: dict) -> tuple[int, str, str]:
        """Admission-plane view: gate occupancy + tier queues, the
        shed/tier counters, and the queue-delay histogram.
        ``?format=json`` returns the raw snapshot."""
        snap = self.admission.snapshot()
        adm = g_stats.prefixed("admission.")
        snap["counters"] = dict(sorted(adm["counters"].items()))
        snap["queue_delay"] = adm["latencies"].get(
            "admission.queue_delay", {})
        if query.get("format") == "json":
            return 200, json.dumps(snap), "application/json"
        qrows = "".join(f"<tr><td>{t}</td><td>{n}</td></tr>"
                        for t, n in snap["queued"].items())
        crows = "".join(f"<tr><td>{k}</td><td>{v}</td></tr>"
                        for k, v in snap["counters"].items()) \
            or "<tr><td colspan=2>none</td></tr>"
        qd = snap["queue_delay"] or {}
        return 200, (
            "<html><head><title>gb admission</title></head><body>"
            "<h1>admission</h1>"
            f"<p>inflight {snap['inflight']}/{snap['max_inflight']}"
            f" &middot; queued {snap['queued_total']}"
            f"/{snap['max_queue']}"
            f" &middot; svc EWMA {snap['svc_ewma_ms']} ms"
            f" &middot; admitted {snap['admitted_total']}"
            f" &middot; shed {snap['shed_total']}</p>"
            "<table border=1><tr><th>tier</th><th>queued</th></tr>"
            f"{qrows}</table>"
            f"<h2>queue delay</h2><p>{json.dumps(qd)}</p>"
            f"<h2>counters</h2><table border=1>{crows}</table>"
            "</body></html>"), "text/html"

    def _page_tenants(self, query: dict) -> tuple[int, str, str]:
        """Tenant-plane view: the resident set with LRU/pin state and
        device bytes (ResidencyManager), cold-start p50/p99, and each
        tenant's admission ledger — weight, share counters, served vs
        shed (the per-tenant SLO burn proxy: shed/(served+shed)).
        ``?format=json`` returns the raw snapshots."""
        from .tenancy import g_residency
        res = g_residency.snapshot()
        adm = self.admission.snapshot().get("tenants", {})
        if query.get("format") == "json":
            return 200, json.dumps(
                {"residency": res, "admission": adm}), \
                "application/json"
        names = sorted(set(res["tenants"]) | set(adm))
        rows = []
        for n in names:
            rt = res["tenants"].get(n, {})
            at = adm.get(n, {})
            served = at.get("served", 0)
            shed = at.get("shed", 0)
            burn = shed / (served + shed) if served + shed else 0.0
            rows.append(
                f"<tr><td>{html_mod.escape(n)}</td>"
                f"<td>{'RESIDENT' if rt.get('resident') else 'parked'}"
                f"{' (pinned)' if rt.get('pinned') else ''}</td>"
                f"<td>{rt.get('device_bytes', 0) / (1 << 20):.2f}</td>"
                f"<td>{rt.get('hits', 0)}</td>"
                f"<td>{rt.get('cold_starts', 0)}</td>"
                f"<td>{at.get('weight', 1.0):g}</td>"
                f"<td>{at.get('inflight', 0)}</td>"
                f"<td>{at.get('queued', 0)}</td>"
                f"<td>{served}</td><td>{shed}</td>"
                f"<td>{100.0 * burn:.1f}%</td></tr>")
        table = "".join(rows) or "<tr><td colspan=11>no tenants</td></tr>"
        return 200, (
            "<html><head><title>gb tenants</title></head><body>"
            "<h1>tenant plane</h1>"
            f"<p>resident {res['resident']}"
            + (f"/{res['max_resident']}" if res['max_resident'] else "")
            + f" &middot; parked {res['parked']}"
            f" &middot; device "
            f"{res['device_bytes'] / (1 << 20):.1f} MB"
            + (f" (cap {res['device_cap'] / (1 << 20):.1f} MB)"
               if res['device_cap'] else "")
            + f" &middot; cold starts {res['coldstarts']}"
            f" (p50 {res['coldstart_p50_ms']:.1f} ms, "
            f"p99 {res['coldstart_p99_ms']:.1f} ms)</p>"
            "<table border=1><tr><th>tenant</th><th>state</th>"
            "<th>device MB</th><th>hits</th><th>cold starts</th>"
            "<th>weight</th><th>inflight</th><th>queued</th>"
            "<th>served</th><th>shed</th><th>shed rate</th></tr>"
            f"{table}</table>"
            "</body></html>"), "text/html"

    def _page_mem(self, query: dict) -> tuple[int, str, str]:
        """Live memory-budget breakdown (the PageStats mem table +
        Mem.cpp printMem role): per-subsystem reserved/gauged bytes
        against the max_mem budget, plus the guardrail counters.
        ``?format=json`` returns the raw snapshot."""
        from ..utils.stats import g_stats
        snap = g_membudget.snapshot()
        counters = g_stats.snapshot()["counters"]
        snap["counters"] = {
            k: v for k, v in sorted(counters.items())
            if k.startswith(("membudget.", "devcheck."))}
        snap["checkify"] = devcheck.enabled()
        if query.get("format") == "json":
            return 200, json.dumps(snap), "application/json"
        mb = lambda n: f"{n / (1 << 20):.2f}"  # noqa: E731
        rows = "".join(
            f"<tr><td>{lb}</td><td>{mb(d['reserved'])}</td>"
            f"<td>{mb(d['gauged'])}</td><td>{d['rejections']}</td></tr>"
            for lb, d in snap["labels"].items())
        crows = "".join(f"<tr><td>{k}</td><td>{v}</td></tr>"
                        for k, v in snap["counters"].items()) \
            or "<tr><td colspan=2>none</td></tr>"
        return 200, (
            "<html><head><title>gb mem</title></head><body>"
            "<h1>memory budget</h1>"
            f"<p>limit {mb(snap['limit'])} MB &middot; "
            f"used {mb(snap['used'])} MB &middot; "
            f"free {mb(snap['free'])} MB &middot; "
            f"high water {mb(snap['high_water'])} MB &middot; "
            f"rejections {snap['rejections']} &middot; "
            f"checkify {'on' if snap['checkify'] else 'off'}</p>"
            "<table border=1><tr><th>label</th><th>reserved MB</th>"
            f"<th>gauged MB</th><th>rejections</th></tr>{rows}</table>"
            f"<h2>guardrail counters</h2>"
            f"<table border=1>{crows}</table>"
            "</body></html>"), "text/html"

    def _fleet_view(self) -> tuple[dict, dict]:
        """(hosts, fleet): per-host ``Stats.wire()`` payloads (None for
        an unreachable host) and their bucket-wise merge. A cluster
        coordinator scrapes every node over ``/rpc/stats``; a
        single-process server is a one-host fleet."""
        from ..utils.stats import g_stats, merge_wire
        if self.cluster is not None:
            sc = self.cluster.scrape()
            return sc["hosts"], sc["fleet"]
        w = g_stats.wire()
        return {"local": w}, merge_wire([w])

    def _page_perf(self, query: dict) -> tuple[int, str, str]:
        """Fleet perf dashboard (PagePerf drawn across hosts + the
        PageStatsdb graphs): one row per latency metric with a p99
        column per host and the MERGED fleet distribution — fleet
        percentiles come from merged histogram buckets, never from
        averaging per-host percentiles. The fleet p99 cell links its
        exemplar trace to /admin/traces; SLO burn rates, gauges,
        counters and qps/p50 sparklines ride below. ``?format=json``
        returns the merged view raw."""
        from ..utils.slo import g_slo
        from ..utils.stats import LatencyStat, g_stats
        hosts, fleet = self._fleet_view()
        # evaluate against the view just scraped so the dashboard is
        # fresh on demand rather than as stale as the last sampler tick
        if g_slo.objectives:
            try:
                g_slo.evaluate(fleet["counters"], fleet["latencies"])
            except Exception:
                g_stats.count("slo.eval_errors")
        slo_status = g_slo.status()
        # operator-visible build alerts: a shard at the runstart pack
        # limit keeps boot-looping on the ValueError until it is split —
        # surface the counter here, where a fleet operator looks first
        alerts = []
        n_ovf = fleet["counters"].get("build.postings_overflow", 0)
        if n_ovf:
            alerts.append({
                "name": "shard_split_needed",
                "count": n_ovf,
                "hint": ("a shard hit the 2^31 stored-postings pack "
                         "limit (build.postings_overflow) — split the "
                         "collection across more shards before the "
                         "node boot-loops"),
            })
        # HBM headroom row from the device telemetry plane: ledger
        # total next to what memory_stats() reports (nulls on a CPU
        # backend / with devwatch off — the row still renders)
        from ..utils import devwatch
        rec = devwatch.reconcile()
        dev0 = rec["devices"][0] if rec["devices"] else {}
        hbm = {"enabled": devwatch.enabled(),
               "ledger_bytes": rec["ledger_bytes"],
               "bytes_in_use": dev0.get("bytes_in_use"),
               "headroom": dev0.get("headroom")}
        if query.get("format") == "json":
            body = {
                "hosts": {
                    a: None if w is None else {
                        k: LatencyStat.from_wire(v).to_dict()
                        for k, v in w.get("latencies", {}).items()}
                    for a, w in hosts.items()},
                "fleet": {
                    "counters": fleet["counters"],
                    "gauges": fleet["gauges"],
                    "latencies": {
                        k: {**st.to_dict(),
                            "exemplars": [
                                {"trace_id": tid, "ms": ms}
                                for _, (tid, ms)
                                in sorted(st.exemplars.items())]}
                        for k, st in fleet["latencies"].items()},
                },
                "slo": slo_status,
                "alerts": alerts,
                "hbm": hbm,
            }
            return 200, json.dumps(body), "application/json"

        pwd = query.get("pwd", "")
        sfx = f"&pwd={urllib.parse.quote(pwd)}" if pwd else ""
        lsfx = f"?pwd={urllib.parse.quote(pwd)}" if pwd else ""
        addrs = sorted(hosts)
        per_host = {
            a: {} if hosts[a] is None else {
                k: LatencyStat.from_wire(v)
                for k, v in hosts[a].get("latencies", {}).items()}
            for a in addrs}
        lat_rows = []
        for name in sorted(fleet["latencies"]):
            st = fleet["latencies"][name]
            cells = "".join(
                f"<td>{per_host[a][name].quantile(0.99):.2f}</td>"
                if name in per_host[a] else "<td>-</td>"
                for a in addrs)
            d = st.to_dict()
            ex = ""
            if st.exemplars:
                tid, _ms = st.exemplars[max(st.exemplars)]
                ex = (f' <a href="/admin/traces?id={tid}{sfx}">'
                      f"ex</a>")
            lat_rows.append(
                f"<tr><td>{name}</td>{cells}"
                f"<td>{d['count']}</td><td>{d['avg_ms']:.2f}</td>"
                f"<td>{d['p50_ms']:.2f}</td>"
                f"<td>{d['p99_ms']:.2f}{ex}</td>"
                f"<td>{d['max_ms']:.2f}</td></tr>")
        hdr = "".join(f"<th>{a} p99</th>" for a in addrs)

        def spark(metric: str, color: str) -> str:
            pts = [(t, m.get(metric))
                   for t, m in g_stats.series(last_s=600)
                   if m.get(metric) is not None]
            if len(pts) < 2:
                return ""
            t0, t1 = pts[0][0], pts[-1][0]
            span = max(t1 - t0, 1.0)
            top = max(v for _, v in pts) or 1.0
            xy = " ".join(f"{(t - t0) / span * 120:.1f},"
                          f"{28.0 - v / top * 24.0:.1f}"
                          for t, v in pts)
            return (f'<svg xmlns="http://www.w3.org/2000/svg" '
                    f'width="124" height="30">'
                    f'<polyline fill="none" stroke="{color}" '
                    f'points="{xy}"/></svg> {metric} (max {top:g})')

        slo_rows = "".join(
            f"<tr><td>{n}</td><td>{st['kind']}</td>"
            f"<td>{st['target']}</td>"
            f"<td>{st['window_total']}</td><td>{st['window_bad']}</td>"
            f"<td>{st['burn_rate']:.3f}</td>"
            f"<td>{st['budget_remaining']:.3f}</td>"
            f"<td>{'BURNING' if st['burning'] else 'ok'}</td></tr>"
            for n, st in sorted(slo_status.items())) \
            or "<tr><td colspan=8>no objectives declared</td></tr>"
        gauge_rows = "".join(
            f"<tr><td>{k}</td><td>{v:g}</td></tr>"
            for k, v in sorted(fleet["gauges"].items()))
        ctr_rows = "".join(
            f"<tr><td>{k}</td><td>{v}</td></tr>"
            for k, v in sorted(fleet["counters"].items()))
        up = sum(1 for w in hosts.values() if w is not None)
        alert_html = "".join(
            f'<p style="color:#fff;background:#c00;padding:6px">'
            f"ALERT {a['name']} (&times;{a['count']}): {a['hint']}</p>"
            for a in alerts)
        return 200, (
            "<html><head><title>gb perf</title></head><body>"
            "<h1>fleet perf</h1>"
            f"{alert_html}"
            f"<p>{up}/{len(hosts)} hosts scraped &middot; "
            f'<a href="/admin/perf?format=json{sfx}">json</a> &middot; '
            f'<a href="/metrics">metrics</a></p>'
            f"<p>HBM: ledger {hbm['ledger_bytes'] >> 20} MB &middot; "
            f"in use {hbm['bytes_in_use'] if hbm['bytes_in_use'] is not None else 'n/a'}"
            f" &middot; headroom "
            f"{hbm['headroom'] if hbm['headroom'] is not None else 'n/a'}"
            f" &middot; devwatch "
            f"{'on' if hbm['enabled'] else 'off'} &middot; "
            f'<a href="/admin/hbm{lsfx}">hbm</a> '
            f'<a href="/admin/device{lsfx}">device</a></p>'
            f"<p>{spark('qps', '#1f77b4')}<br>"
            f"{spark('p50_ms', '#d62728')}</p>"
            f"<h2>latencies (ms)</h2>"
            f"<table border=1><tr><th>metric</th>{hdr}"
            "<th>fleet n</th><th>avg</th><th>p50</th><th>p99</th>"
            f"<th>max</th></tr>{''.join(lat_rows)}</table>"
            "<h2>SLOs</h2>"
            "<table border=1><tr><th>objective</th><th>kind</th>"
            "<th>target</th><th>window n</th><th>bad</th>"
            "<th>burn rate</th><th>budget left</th><th></th></tr>"
            f"{slo_rows}</table>"
            f"<h2>gauges</h2><table border=1>{gauge_rows}</table>"
            f"<h2>counters</h2><table border=1>{ctr_rows}</table>"
            "</body></html>"), "text/html"

    def _metrics_text(self) -> str:
        """Prometheus-style text exposition of the merged fleet view.
        Histogram buckets carry OpenMetrics-style exemplar suffixes
        (``# {trace_id="..."} <ms>``) where a sampled trace landed in
        the bucket. Metric names ride in a ``name`` label so dotted
        internal names pass through unmangled."""
        from ..utils.stats import _bucket_bounds
        hosts, fleet = self._fleet_view()
        lines = [
            "# HELP osse_latency_ms merged fleet latency histogram (ms)",
            "# TYPE osse_latency_ms histogram",
        ]
        for name in sorted(fleet["latencies"]):
            st = fleet["latencies"][name]
            cum = 0
            for idx in sorted(st.buckets):
                cum += st.buckets[idx]
                hi = _bucket_bounds(idx)[1]
                line = (f'osse_latency_ms_bucket{{name="{name}",'
                        f'le="{hi:g}"}} {cum}')
                ex = st.exemplars.get(idx)
                if ex is not None:
                    line += f' # {{trace_id="{ex[0]}"}} {ex[1]:g}'
                lines.append(line)
            lines.append(f'osse_latency_ms_bucket{{name="{name}",'
                         f'le="+Inf"}} {st.count}')
            lines.append(f'osse_latency_ms_sum{{name="{name}"}} '
                         f"{st.total_ms:g}")
            lines.append(f'osse_latency_ms_count{{name="{name}"}} '
                         f"{st.count}")
        # per-tenant request outcomes as proper labels (the quota
        # plane's scrape surface), parsed back out of the dotted
        # admission.tenant.<t>.<outcome> counter namespace
        lines.append("# TYPE osse_tenant_requests_total counter")
        for k, v in sorted(fleet["counters"].items()):
            if not k.startswith("admission.tenant."):
                continue
            t, _, outcome = k[len("admission.tenant."):].rpartition(".")
            if t and outcome in ("served", "shed"):
                lines.append(
                    f'osse_tenant_requests_total{{tenant="{t}",'
                    f'outcome="{outcome}"}} {v}')
        lines.append("# TYPE osse_counter counter")
        lines.extend(f'osse_counter{{name="{k}"}} {v}'
                     for k, v in sorted(fleet["counters"].items()))
        lines.append("# TYPE osse_gauge gauge")
        lines.extend(f'osse_gauge{{name="{k}"}} {v:g}'
                     for k, v in sorted(fleet["gauges"].items()))
        # per-(collection, plane) device residency from the HBM
        # ledger (OSSE_DEVWATCH=1; empty rows when off) — the tenant
        # plane's byte-bounded residency, scrape-visible fleet-wide
        from ..utils import devwatch
        lines.append("# TYPE osse_hbm_bytes gauge")
        for c, planes in sorted(
                devwatch.g_devwatch.ledger_snapshot().items()):
            for p, cols in sorted(planes.items()):
                lines.append(f'osse_hbm_bytes{{collection="{c}",'
                             f'plane="{p}"}} {sum(cols.values())}')
        lines.append(f"osse_hosts_scraped "
                     f"{sum(1 for w in hosts.values() if w is not None)}")
        return "\n".join(lines) + "\n"

    def _page_transport(self, query: dict) -> tuple[int, str, str]:
        """Cluster transport health (the PagePerf slice of the
        Multicast/UdpServer role): per-peer connection pool + RTT
        EWMAs, hedge fired/won counters, connection reuse/dial/retry
        counts, and the hostmap's twin-preference state. JSON, like
        /admin/hosts and /admin/perf."""
        from ..parallel.transport import g_transport
        from ..utils.stats import g_stats
        body = g_stats.prefixed("transport.")
        tr = (self.cluster.transport if self.cluster is not None
              else g_transport)
        body["peers"] = tr.stats()
        if self.cluster is not None:
            hm = self.cluster.hostmap
            body["hostmap"] = {
                f"shard{s}": {
                    "twin_order": hm.twin_order(s),
                    "alive": [bool(a) for a in hm.alive[s]],
                    "rtt_ms": [round(1000.0 * float(v), 3)
                               for v in hm.rtt_s[s]],
                    "addrs": self.cluster.conf.addresses[s],
                } for s in range(hm.n_shards)}
        return 200, json.dumps(body), "application/json"

    def _page_cache(self, query: dict) -> tuple[int, str, str]:
        """The cache plane's admin page (the PageStats cache table
        role): every registered cache with entries/bytes/hit rate/
        generation, a per-cache flush link and a flush-all link.
        ``?format=json`` returns the raw snapshot + the ``cache.*``
        metric namespace; ``?flush=<name>`` / ``?flush=all`` flushes."""
        from ..cache import g_cacheplane
        from ..utils.stats import g_stats
        flush = query.get("flush", "")
        flushed = None
        if flush:
            flushed = g_cacheplane.flush(
                None if flush == "all" else flush)
        snap = g_cacheplane.snapshot()
        if query.get("format") == "json":
            body = {"caches": snap,
                    "enabled": g_cacheplane.enabled,
                    "metrics": g_stats.prefixed("cache.")}
            if flushed is not None:
                body["flushed_bytes"] = flushed
            return 200, json.dumps(body), "application/json"
        pwd = query.get("pwd", "")
        sfx = f"&pwd={urllib.parse.quote(pwd)}" if pwd else ""
        rows = "".join(
            f"<tr><td>{nm}</td><td>{st['entries']}</td>"
            f"<td>{st['bytes'] / (1 << 10):.1f}</td>"
            f"<td>{st['hits']}</td><td>{st['misses']}</td>"
            f"<td>{100.0 * st['hit_rate']:.1f}%</td>"
            f"<td>{st['evictions']}</td><td>{st['stale_served']}</td>"
            f"<td><code>{st['generation']}</code></td>"
            f"<td>{'on' if st['enabled'] else 'off'}</td>"
            f"<td><a href=\"/admin/cache?flush="
            f"{urllib.parse.quote(nm)}{sfx}\">flush</a></td></tr>"
            for nm, st in snap.items()) \
            or "<tr><td colspan=11>no registered caches</td></tr>"
        note = (f"<p>flushed {flushed} bytes</p>"
                if flushed is not None else "")
        return 200, (
            "<html><head><title>gb cache</title></head><body>"
            "<h1>cache plane</h1>"
            f"<p>plane {'enabled' if g_cacheplane.enabled else 'DISABLED'}"
            f" &middot; <a href=\"/admin/cache?flush=all{sfx}\">"
            "flush all</a></p>" + note +
            "<table border=1><tr><th>cache</th><th>entries</th>"
            "<th>KB</th><th>hits</th><th>misses</th><th>hit rate</th>"
            "<th>evict</th><th>stale</th><th>generation</th>"
            f"<th>enabled</th><th></th></tr>{rows}</table>"
            "</body></html>"), "text/html"

    def _page_jit(self, query: dict) -> tuple[int, str, str]:
        """Compile/retrace/transfer attribution from the jit watcher
        (OSSE_JITWATCH=1): every event keyed by (function,
        shape-signature, call-site), so a steady-state retrace or a
        hidden host sync names its line. ``?format=json`` returns the
        raw snapshot."""
        from ..utils import jitwatch
        from ..utils.stats import g_stats
        snap = jitwatch.snapshot()
        counters = g_stats.snapshot()["counters"]
        snap["counters"] = {k: v for k, v in sorted(counters.items())
                            if k.startswith("jit.")}
        if query.get("format") == "json":
            return 200, json.dumps(snap), "application/json"
        t = snap["totals"]
        rows = "".join(
            f"<tr><td>{e['kind']}</td><td>{e['fn']}</td>"
            f"<td>{e['site']}</td><td>{e['count']}</td>"
            f"<td>{e['bytes']}</td>"
            f"<td>{'yes' if e['boundary'] else 'NO'}</td>"
            f"<td>{e['shapes'] or e['last']}</td></tr>"
            for e in snap["events"]) \
            or "<tr><td colspan=7>none</td></tr>"
        return 200, (
            "<html><head><title>gb jit</title></head><body>"
            "<h1>jit plane</h1>"
            f"<p>watcher {'enabled' if snap['enabled'] else 'DISABLED'}"
            f" &middot; compiles {t['compiles']}"
            f" &middot; first traces {t['first_traces']}"
            f" &middot; retraces {t['retraces']}"
            f" &middot; transfers {t['transfers']}"
            f" (off-boundary {t['transfers_offboundary']})</p>"
            "<table border=1><tr><th>kind</th><th>fn</th><th>site</th>"
            "<th>count</th><th>bytes</th><th>boundary</th>"
            f"<th>detail</th></tr>{rows}</table>"
            "</body></html>"), "text/html"

    def _page_hbm(self, query: dict) -> tuple[int, str, str]:
        """HBM ledger (OSSE_DEVWATCH=1): every registered device
        buffer by (collection, plane, column), plane totals, and the
        reconciliation against ``device.memory_stats()`` — live bytes
        the ledger cannot name are allocator slack + unregistered
        temporaries (the fragmentation column). ``?format=json``
        returns the raw ledger."""
        from ..utils import devwatch
        snap = devwatch.snapshot()
        body = {k: snap[k] for k in ("enabled", "ledger", "planes",
                                     "collections", "total_bytes",
                                     "reconcile")}
        if query.get("format") == "json":
            return 200, json.dumps(body), "application/json"
        rows = "".join(
            f"<tr><td>{c}</td><td>{p}</td><td>{col}</td>"
            f"<td>{n}</td></tr>"
            for c, planes in sorted(snap["ledger"].items())
            for p, cols in sorted(planes.items())
            for col, n in sorted(cols.items())) \
            or "<tr><td colspan=4>none</td></tr>"
        dev_rows = "".join(
            f"<tr><td>{d['device']}</td><td>{d['kind']}</td>"
            f"<td>{d['bytes_in_use']}</td>"
            f"<td>{d['peak_bytes_in_use']}</td>"
            f"<td>{d['headroom']}</td>"
            f"<td>{d['fragmentation']}</td></tr>"
            for d in snap["reconcile"]["devices"]) \
            or "<tr><td colspan=6>no devices</td></tr>"
        planes = " &middot; ".join(
            f"{p}: {n >> 20} MB"
            for p, n in sorted(snap["planes"].items())) or "empty"
        return 200, (
            "<html><head><title>gb hbm</title></head><body>"
            "<h1>HBM ledger</h1>"
            f"<p>devwatch {'enabled' if snap['enabled'] else 'DISABLED'}"
            f" &middot; ledger {snap['total_bytes'] >> 20} MB"
            f" &middot; {planes}</p>"
            "<table border=1><tr><th>collection</th><th>plane</th>"
            f"<th>column</th><th>bytes</th></tr>{rows}</table>"
            "<h2>memory_stats reconciliation</h2>"
            "<table border=1><tr><th>device</th><th>kind</th>"
            "<th>bytes_in_use</th><th>peak</th><th>headroom</th>"
            f"<th>fragmentation</th></tr>{dev_rows}</table>"
            "</body></html>"), "text/html"

    def _page_device(self, query: dict) -> tuple[int, str, str]:
        """Wave flight recorder + roofline attribution
        (OSSE_DEVWATCH=1): the recorder ring's issue→wait→collect
        waterfall with per-round escalations, and the per-(kernel,
        shape-bucket) flops/bytes verdicts against the backend peaks.
        ``?format=json`` returns the raw ring + cost table."""
        from ..utils import devwatch
        snap = devwatch.snapshot()
        body = {k: snap[k] for k in ("enabled", "totals", "waves",
                                     "rooflines", "peaks")}
        # each resident index's own count of dispatches by (program,
        # shape bucket): kept in DeviceIndex._costed, devwatch or not
        body["dispatches"] = []
        # ... and beside it, which kernel each query was first routed
        # to (``DeviceIndex.route_counts``; ``devindex.route.*`` in
        # g_stats are the same bumps, summed over collections)
        body["routes"] = {}
        for name, coll in sorted(dict(self.colldb.colls).items()):
            di = getattr(coll, "_device_index", None)
            if di is not None:
                body["routes"][name] = dict(di.route_counts)
            for (prog, bucket), n in sorted(
                    dict(di.dispatches if di is not None else {}).items()):
                body["dispatches"].append(
                    {"coll": name, "program": prog,
                     "bucket": list(bucket), "dispatches": n})
        if query.get("format") == "json":
            return 200, json.dumps(body), "application/json"
        waves = list(snap["waves"])[-64:]
        scale = max((w["total_s"] for w in waves), default=0.0) or 1e-9

        def bar(w):
            return "".join(
                f'<div style="display:inline-block;height:10px;'
                f'width:{max(1, int(300 * w[f] / scale))}px;'
                f'background:{c}"></div>'
                for f, c in (("issue_s", "#4c78a8"),
                             ("wait_s", "#eeca3b"),
                             ("collect_s", "#e45756")))
        rows = "".join(
            f"<tr><td>{w['seq']}</td><td>{w['source']}</td>"
            f"<td>{w.get('coll', '')}</td>"
            f"<td>{w.get('plans', w.get('tickets', ''))}</td>"
            f"<td>{w['total_s'] * 1000:.1f}</td><td>{bar(w)}</td>"
            f"<td>{len(w['rounds'])}</td>"
            f"<td>{sum(r.get('escalations', 0) for r in w['rounds'])}"
            f"</td><td>{w['error'] or ''}</td></tr>"
            for w in reversed(waves)) \
            or "<tr><td colspan=9>none</td></tr>"
        roof = "".join(
            f"<tr><td>{e['kernel']}</td><td>{e['bucket']}</td>"
            f"<td>{e['flops']:.3g}</td><td>{e['bytes']:.3g}</td>"
            f"<td>{e['intensity']:.2f}</td><td>{e['ridge']:.2f}</td>"
            f"<td>{e['verdict']}</td>"
            f"<td>{e['modeled_bytes'] or ''}</td>"
            f"<td>{e['dispatches']}</td></tr>"
            for e in snap["rooflines"]) \
            or "<tr><td colspan=9>none</td></tr>"
        disp = "".join(
            f"<tr><td>{d['coll']}</td><td>{d['program']}</td>"
            f"<td>{d['bucket']}</td><td>{d['dispatches']}</td></tr>"
            for d in body["dispatches"]) \
            or "<tr><td colspan=4>none</td></tr>"
        routes = "".join(
            f"<tr><td>{c}</td><td>{r['f1']}</td><td>{r['fd']}</td>"
            f"<td>{r['f2']}</td></tr>"
            for c, r in body["routes"].items()) \
            or "<tr><td colspan=4>none</td></tr>"
        pk = snap["peaks"]
        return 200, (
            "<html><head><title>gb device</title></head><body>"
            "<h1>device plane</h1>"
            f"<p>devwatch {'enabled' if snap['enabled'] else 'DISABLED'}"
            f" &middot; waves {snap['totals']['waves']}"
            f" &middot; rounds {snap['totals']['rounds']}"
            f" &middot; errors {snap['totals']['wave_errors']}"
            f" &middot; peaks {pk['label']}"
            f" ({pk['flops']:.3g} FLOP/s, {pk['bw']:.3g} B/s"
            f"{', assumed' if pk['assumed'] else ''})</p>"
            "<h2>wave waterfall (issue / wait / collect)</h2>"
            "<table border=1><tr><th>seq</th><th>source</th>"
            "<th>coll</th><th>plans</th><th>ms</th><th>split</th>"
            "<th>rounds</th><th>escalations</th><th>error</th></tr>"
            f"{rows}</table>"
            "<h2>roofline per (kernel, shape bucket)</h2>"
            "<table border=1><tr><th>kernel</th><th>bucket</th>"
            "<th>flops</th><th>bytes</th><th>intensity</th>"
            "<th>ridge</th><th>verdict</th><th>modeled bytes</th>"
            f"<th>dispatches</th></tr>{roof}</table>"
            "<h2>dispatches per (program, shape bucket)</h2>"
            "<table border=1><tr><th>coll</th><th>program</th>"
            f"<th>bucket</th><th>dispatches</th></tr>{disp}</table>"
            "<h2>first route per query</h2>"
            "<table border=1><tr><th>coll</th><th>f1</th><th>fd</th>"
            f"<th>f2</th></tr>{routes}</table>"
            "</body></html>"), "text/html"

    #: waterfall bar palette — one color per host, assigned by hash so
    #: the same host colors the same across traces
    _TRACE_COLORS = ("#4c78a8", "#f58518", "#54a24b", "#e45756",
                     "#72b7b2", "#b279a2", "#eeca3b", "#9d755d")

    def _page_traces(self, query: dict) -> tuple[int, str, str]:
        """Recent sampled traces + the slow-query log, with a per-trace
        waterfall (nested HTML bars, offsets/widths proportional to the
        span's place in the trace, colored by host/shard).

        ``?id=<trace_id>`` shows one trace; ``?format=json`` returns
        the raw ring + slowlog tail."""
        recent = g_tracer.recent()
        slowlog = g_tracer.slowlog_tail(50)
        if query.get("format") == "json":
            return 200, json.dumps(
                {"recent": recent, "slowlog": slowlog,
                 "sample_n": g_tracer.sample_n,
                 "slow_ms": g_tracer.slow_ms}), "application/json"
        tid = query.get("id", "")
        if tid:
            tr = g_tracer.find(tid) or next(
                (t for t in reversed(slowlog)
                 if t.get("trace_id") == tid), None)
            if tr is None:
                return 404, json.dumps({"error": "no such trace"}), \
                    "application/json"
            return 200, (
                "<html><head><title>trace</title></head><body>"
                f"{self._trace_waterfall(tr)}"
                '<p><a href="/admin/traces">all traces</a></p>'
                "</body></html>"), "text/html"
        blocks = "".join(self._trace_waterfall(t)
                         for t in reversed(recent[-20:]))
        slows = "".join(
            f'<tr><td><a href="/admin/traces?id='
            f'{html_mod.escape(str(t.get("trace_id", "")))}">'
            f'{html_mod.escape(str(t.get("trace_id", "")))}</a></td>'
            f'<td>{html_mod.escape(str((t.get("root") or {}).get("tags", {}).get("q", "")))}</td>'
            f'<td>{t.get("dur_ms", 0):.1f}</td></tr>'
            for t in reversed(slowlog)) \
            or "<tr><td colspan=3>empty</td></tr>"
        return 200, (
            "<html><head><title>gb traces</title></head><body>"
            "<h1>traces</h1>"
            f"<p>sampling 1/{g_tracer.sample_n} &middot; slow &ge; "
            f"{g_tracer.slow_ms:.0f} ms &middot; ring "
            f"{len(recent)}</p>"
            "<h2>slow queries (slowlog.jsonl)</h2>"
            "<table border=1><tr><th>trace</th><th>q</th>"
            f"<th>ms</th></tr>{slows}</table>"
            f"<h2>recent traces</h2>{blocks}"
            "</body></html>"), "text/html"

    def _trace_waterfall(self, tr: dict) -> str:
        """One trace → nested HTML bars. Bar offset/width are percent
        of the trace duration; color keys on the span's host."""
        total = max(float(tr.get("dur_ms", 0.0)), 1e-3)
        rows: list[str] = []

        def color(host: str) -> str:
            return self._TRACE_COLORS[hash(host) %
                                      len(self._TRACE_COLORS)]

        def walk(node: dict, depth: int) -> None:
            left = 100.0 * max(float(node.get("start_ms", 0.0)), 0.0) \
                / total
            width = min(100.0 - left,
                        100.0 * float(node.get("dur_ms", 0.0)) / total)
            host = str(node.get("host", ""))
            tags = node.get("tags") or {}
            tagstr = " ".join(f"{k}={v}" for k, v in tags.items())
            label = html_mod.escape(
                f"{node.get('name', '?')} {node.get('dur_ms', 0):.2f}ms"
                + (f" [{host}]" if host else "")
                + (f" {tagstr}" if tagstr else ""))
            rows.append(
                f'<div style="position:relative;height:16px;'
                f'margin-left:{depth * 12}px">'
                f'<div title="{label}" style="position:absolute;'
                f"left:{left:.2f}%;width:{max(width, 0.2):.2f}%;"
                f"height:14px;background:{color(host)};"
                f'overflow:hidden;font-size:10px;color:#fff;'
                f'white-space:nowrap">{label}</div></div>')
            for c in node.get("children", []):
                walk(c, depth + 1)

        root = tr.get("root") or {}
        walk(root, 0)
        head = (f'trace <b>{html_mod.escape(str(tr.get("trace_id")))}'
                f"</b> &middot; {total:.1f} ms"
                + (" &middot; <b>slow</b>" if tr.get("slow") else ""))
        return (f'<div style="border:1px solid #ccc;margin:8px;'
                f'padding:4px"><p>{head}</p>{"".join(rows)}</div>')

    def _page_profiler(self, query: dict) -> tuple[int, str, str]:
        """Per-stage timing table + on-demand SAMPLING profiler (the
        two halves of the Profiler.cpp role: the message-latency stats
        and the realtime stack sampler started/stopped from the admin
        page — ``startRealTimeProfiler``, ``Profiler.cpp:1586``).

        ``?sample=start|stop|reset`` controls the sampler;
        ``?sample=report`` (or format=json with the sampler running)
        returns the aggregated stack histogram."""
        from ..utils.profiler import g_profiler
        from ..utils.stats import g_stats
        action = query.get("sample", "")
        if action == "start":
            g_profiler.start()
            return 200, json.dumps({"sampling": True}), \
                "application/json"
        if action == "stop":
            g_profiler.stop()
            return 200, json.dumps(g_profiler.report()), \
                "application/json"
        if action == "reset":
            g_profiler.reset()
            return 200, json.dumps({"reset": True}), "application/json"
        if action == "report":
            return 200, json.dumps(g_profiler.report()), \
                "application/json"
        snap = g_stats.snapshot()
        if query.get("format") == "json":
            return 200, json.dumps(snap["latencies"]), "application/json"
        rows = "".join(
            f"<tr><td>{html_mod.escape(k)}</td><td>{v['count']}</td>"
            f"<td>{v['avg_ms']:.1f}</td><td>{v['p50_ms']:.1f}</td>"
            f"<td>{v['p99_ms']:.1f}</td><td>{v['max_ms']:.1f}</td></tr>"
            for k, v in sorted(snap["latencies"].items()))
        return 200, (
            "<html><head><title>profiler</title></head><body>"
            "<h1>stage timings (ms)</h1><table border=1>"
            "<tr><th>stage</th><th>n</th><th>avg</th><th>p50</th>"
            f"<th>p99</th><th>max</th></tr>{rows}</table>"
            "</body></html>"), "text/html"

    def _page_graph(self) -> str:
        """qps/latency time-series as inline SVG (PagePerf/Statsdb
        graphs without image deps)."""
        from ..utils.stats import g_stats
        series = g_stats.series(last_s=3600)
        w, h = 600, 160
        if not series:
            return (f'<svg xmlns="http://www.w3.org/2000/svg" '
                    f'width="{w}" height="{h}"><text x="10" y="20">'
                    f"no samples yet</text></svg>")
        t0, t1 = series[0][0], series[-1][0]
        span = max(t1 - t0, 1.0)

        def poly(metric: str, color: str) -> str:
            pts = [(t, m.get(metric)) for t, m in series
                   if m.get(metric) is not None]
            if not pts:
                return ""
            top = max(v for _, v in pts) or 1.0
            xy = " ".join(
                f"{10 + (t - t0) / span * (w - 20):.1f},"
                f"{h - 20 - v / top * (h - 40):.1f}" for t, v in pts)
            return (f'<polyline fill="none" stroke="{color}" '
                    f'points="{xy}"/>'
                    f'<text x="12" y="{h - 6}" fill="{color}" '
                    f'font-size="10">{metric} (max {top:.1f})</text>')
        return (f'<svg xmlns="http://www.w3.org/2000/svg" width="{w}" '
                f'height="{h}" style="background:#fff">'
                + poly("qps", "#1f77b4") + poly("p50_ms", "#d62728")
                + "</svg>")

    # --- statsdb persistence (Statsdb.cpp sample ring) -------------------

    def _sample_loop(self) -> None:
        from ..utils.stats import g_stats
        self._lines_written = 0
        last_q = self.stats["queries"]
        last_t = time.monotonic()
        while not self._stop_sampling.wait(10.0):
            now = time.monotonic()
            dq = self.stats["queries"] - last_q
            qps = dq / max(now - last_t, 1e-9)
            last_q, last_t = self.stats["queries"], now
            full = g_stats.snapshot()
            snap = full["latencies"].get("query.device_batch") or {}
            # guardrail counters ride the same sample ring so PagePerf
            # graphs budget pressure and check trips over time
            rejects = full["counters"].get("membudget.reject", 0)
            trips = full["counters"].get("devcheck.trip", 0)
            g_stats.sample(qps=round(qps, 2),
                           p50_ms=round(snap.get("p50_ms", 0.0), 1),
                           budget_rejects=rejects, check_trips=trips)
            # SLO tick: objectives consume the merged fleet stream on
            # a coordinator, the local registry otherwise; a scrape
            # failure costs one tick, never the sampler thread
            try:
                from ..utils.slo import g_slo
                if g_slo.objectives:
                    if self.cluster is not None:
                        fl = self.cluster.scrape()["fleet"]
                        g_slo.evaluate(fl["counters"],
                                       fl["latencies"])
                    else:
                        g_slo.evaluate()
            except Exception:  # noqa: BLE001 — keep sampling
                g_stats.count("slo.eval_errors")
            try:
                with open(self._statsdb_path, "a",
                          encoding="utf-8") as fh:
                    fh.write(json.dumps(
                        [time.time(), {"qps": round(qps, 2),
                                       "budget_rejects": rejects,
                                       "check_trips": trips}]) + "\n")
                self._lines_written += 1
                if self._lines_written >= 512:  # it IS a ring: rotate
                    tail = self._statsdb_path.read_text(
                        encoding="utf-8").splitlines()[-2000:]
                    self._statsdb_path.write_text(
                        "\n".join(tail) + "\n", encoding="utf-8")
                    self._lines_written = 0
            except OSError:
                pass

    def _load_statsdb(self) -> None:
        from ..utils.stats import g_stats
        if not self._statsdb_path.exists():
            return
        try:
            lines = self._statsdb_path.read_text(
                encoding="utf-8", errors="replace").splitlines()[-500:]
        except OSError:
            return
        # per-line tolerance: a kill-9 mid-append leaves ONE torn line;
        # it must cost one sample, not the whole ring
        for line in lines:
            if not line.strip():
                continue
            try:
                t, m = json.loads(line)
                g_stats.timeseries.append((float(t), m))
            except Exception:  # noqa: BLE001 — torn/corrupt line
                g_stats.count("statsdb.corrupt_lines")

    def _page_hosts(self) -> str:
        """Shard/cluster map (PageHosts.cpp)."""
        if self.sharded is None:
            return json.dumps({"shards": 1, "mode": "single"})
        hm = self.sharded.hostmap
        return json.dumps({
            "shards": hm.n_shards,
            "replicas": hm.n_replicas,
            "alive": hm.alive.tolist(),
            "docsPerShard": [c.num_docs for c in self.sharded.shards],
        })

    # --- lifecycle --------------------------------------------------------

    def start(self) -> None:
        from ..utils import devwatch, jitwatch
        jitwatch.maybe_enable()
        devwatch.maybe_enable()  # OSSE_DEVWATCH=1 arms the hbm plane
        chaos_mod.maybe_enable()  # OSSE_CHAOS=<seed> arms the plane
        # the ROADMAP traffic-plane objective, declared by default so
        # every server exports slo.query_p99.* from boot; operators
        # can declare richer objectives before start()
        from ..utils.slo import g_slo
        if not g_slo.objectives:
            g_slo.declare_latency(
                "query_p99",
                "cluster.query" if self.cluster is not None
                else "serve.search",
                threshold_ms=500.0, target=0.99)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route to our logger
                log.debug("%s " + fmt, self.client_address[0], *args)

            def parse_request(self):
                # the request line is read: serve.request starts here
                self._t_request = time.perf_counter()
                return super().parse_request()

            def _serve(self, method: str):
                parsed = urllib.parse.urlsplit(self.path)
                query = dict(urllib.parse.parse_qsl(parsed.query))
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                try:
                    nice = int(self.headers.get("X-Niceness") or 0)
                except ValueError:
                    nice = 0
                # a scatter leg carries its coordinator's tier and
                # tenant verdicts
                tier = priority_mod.tier_from_header(
                    self.headers.get(priority_mod.PRIORITY_HEADER))
                tenant = priority_mod.tenant_from_header(
                    self.headers.get(priority_mod.TENANT_HEADER))
                # the request's stage ledger rides this thread's
                # context; the trace that /search opens takes it up
                ledger = trace_mod.StageLedger()
                with trace_mod.bind_ledgers((ledger,)):
                    status, payload, ctype = outer.handle(
                        method, parsed.path, query, body,
                        client_ip=self.client_address[0],
                        niceness=nice, tier=tier, tenant=tenant)
                data = payload.encode("utf-8")
                self.send_response(status)
                self.send_header("Content-Type", ctype + "; charset=utf-8")
                self.send_header("Content-Length", str(len(data)))
                # shed 503s stash Retry-After on the side channel
                # (handle() runs on this thread, so the contextvar set
                # inside it is visible here)
                for hname, hval in admission_mod.pop_response_headers():
                    self.send_header(hname, hval)
                self.end_headers()
                self.wfile.write(data)
                if parsed.path == "/search":
                    # last byte written: the timeline closes
                    trace_mod.finish_request(ledger, self._t_request)

            def do_GET(self):
                self._serve("GET")

            def do_POST(self):
                self._serve("POST")

        self._warm_device()
        self._httpd = _FrontDoorHTTPServer((self.host, self.port), Handler)
        # TLS plane (reference links -lssl and serves https off gb.pem,
        # TcpServer.cpp / Makefile:113): wrap the listening socket when
        # a cert is configured — same handler, same port semantics
        cert = getattr(self.conf, "ssl_cert", "") or ""
        if cert:
            import ssl as _ssl
            ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(
                cert, keyfile=getattr(self.conf, "ssl_key", "") or None)
            # handshake on first READ (in the per-connection handler
            # thread), NOT in accept(): a stalled ClientHello must not
            # block the single accept loop for every other client
            self._httpd.socket = ctx.wrap_socket(
                self._httpd.socket, server_side=True,
                do_handshake_on_connect=False)
            log.info("TLS enabled (cert=%s)", cert)
        self.port = self._httpd.server_address[1]  # resolve port 0
        g_tracer.configure(host=f"{self.host}:{self.port}")
        self._thread = threads.spawn(f"httpd-{self.port}",
                                     self._httpd.serve_forever)
        if not self._batcher.alive:  # stop()/start() cycle
            self._batcher = QueryBatcher(self._run_device_batch)
        self._load_statsdb()
        self._stop_sampling.clear()
        self._sampler = threads.spawn("statsdb", self._sample_loop)
        log.info("http server on %s:%d", self.host, self.port)

    def _warm_device(self) -> None:
        """Start-up's cold start, before the socket listens: every
        collection this server already holds pages of gets its device
        base built, its closed F1 program set dispatched once
        (``DeviceIndex.warm_f1``: a compile or a cache load each) and
        its resident loop — so a listening server means that no F1
        query of the set can meet a compile behind the batcher's and
        the loop's waits, which are shorter than one. A collection
        filled later is promoted by its first request, as before, and
        compiles what its queries hit. FD's programs are not part of
        it (90 s each: ROADMAP S1)."""
        if (not self.conf.serve_device or self.cluster is not None
                or self.sharded is not None):
            return
        names = set(self.colldb.colls)
        if "main" in self.colldb.names():
            names.add("main")
        hot = int(getattr(self.conf, "tenant_hot", 0))
        for name in sorted(names)[:hot or None]:
            coll = self.colldb.get(name, create=False)
            if not coll.num_docs:
                continue
            try:
                engine.get_resident_loop(coll, warm=True)
            except Exception as e:  # noqa: BLE001 -- serve cold instead
                log.warning("device warm-up of %r failed (%r); its first "
                            "request cold-starts it", name, e)

    def stop(self) -> None:
        self._stop_sampling.set()
        self._batcher.stop()
        # park every resident tenant with the batcher that fed it (the
        # residency manager keeps the records, so a start()/stop()
        # cycle cold-starts cleanly from the devcache base)
        from .tenancy import g_residency
        g_residency.stop_all()
        if self.sharded is not None:
            # mesh serving plane: stop its loop too (lazily respawned
            # by MeshResident.serve_loop on restart)
            mr = getattr(self.sharded, "_mesh_resident", None)
            if mr is not None:
                mr.stop()
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


def serve(base_dir, host: str = "127.0.0.1", port: int = 8000,
          sharded=None) -> SearchHTTPServer:
    s = SearchHTTPServer(base_dir, host, port, sharded=sharded)
    s.start()
    return s
