"""Tenant plane — device residency manager for 1k-collection serving.

Reference: Gigablast's product was "a custom search engine per
customer" — ``Collectiondb.cpp`` holds multi-tenant CollectionRecs,
each owning a per-collection RdbBase for every database, created by
``addColl`` and torn down by ``delColl``; the crawlbot API
(``PageCrawlBot.cpp``) minted one per REST job. The TPU port's analog
of an RdbBase is much more expensive: a hot collection owns an
HBM-resident :class:`~..query.devindex.DeviceIndex` plus an
always-running :class:`~..query.resident.ResidentLoop`, and before
this module nothing ever released either (engine.get_device_index /
get_resident_loop cached them on the Collection forever) — a few
hundred tenants would exhaust HBM long before the ~1k-collection
scale the ROADMAP asks for.

:class:`ResidencyManager` owns that lifecycle now:

* **LRU-with-pinning hot set.** Every resident tenant is tracked with
  a recency sequence; the set is sized two ways — a count bound
  (``max_resident``, the ``tenant_hot`` parm) and the membudget
  "device" label's soft cap (``set_label_cap``), which sums real
  ``resident_bytes()`` per tenant. ``pin()`` exempts a tenant from
  eviction (the "main" collection of a single-tenant box).
* **Cheap parked state.** Eviction stops the loop and drops the
  device arrays (the gauge goes to zero), but the HOST side of the
  packed columns survives in the DeviceIndex disk base cache
  (``posdb.dir/devcache/base_<fp>.npz``), so a cold start re-enters
  at transfer speed instead of repaying the O(corpus) repack.
* **Single-flight cold start.** Concurrent queries to a cold tenant
  trigger ONE build; riders wait on the leader's flight under their
  own deadline and shed (DeadlineExceeded → the serve edge's
  stale-or-504 ladder) if the budget burns first. The cold start
  itself runs under the caller's admitted token — the admission gate
  already sits in front of every serve-path query.
* **Pressure eviction.** The manager registers as a LOW-priority
  membudget pressure handler, so device pressure sheds cold tenants
  before the cache plane flushes and long before real work is
  refused (the shed-before-refuse ladder, one rung lower).

``/admin/tenants`` (serve/server.py) renders :meth:`snapshot`;
``tests/test_tenancy.py`` drives a seeded Zipf stream over more
collections than slots through the front door and holds the invariants.
"""

from __future__ import annotations

import time
from collections import deque

from ..utils import deadline as deadline_mod
from ..utils import devwatch
from ..utils import trace as trace_mod
from ..utils.lockcheck import make_event, make_rlock
from ..utils.log import get_logger
from ..utils.membudget import g_membudget
from ..utils.stats import g_stats

log = get_logger("tenancy")

#: riders without a deadline bound their flight wait here (matches
#: Ticket.wait's default — a wedged build must not hang callers forever)
COLD_WAIT_S = 120.0

#: pressure-handler priority: BELOW the cache plane's default (100) so
#: cold tenants shed first — a parked tenant costs one transfer-speed
#: cold start; a flushed cache costs every hot SERP a recompute
PRESSURE_PRIORITY = 10


class _Tenant:
    """One collection's residency record."""

    __slots__ = ("name", "coll", "loop", "pinned", "parked", "seq",
                 "nbytes", "hits", "cold_starts", "promoted_at")

    def __init__(self, name: str, coll):
        self.name = name
        self.coll = coll
        self.loop = None
        self.pinned = False
        self.parked = False
        self.seq = 0
        self.nbytes = 0
        self.hits = 0
        self.cold_starts = 0
        self.promoted_at = 0.0


class _Flight:
    """A single-flight cold start: the leader builds, riders wait."""

    __slots__ = ("ev", "loop", "err")

    def __init__(self):
        self.ev = make_event("tenancy.flight")
        self.loop = None
        self.err: BaseException | None = None


class ResidencyManager:
    """Owns the collection → (DeviceIndex, ResidentLoop) hot set."""

    def __init__(self, max_resident: int = 0):
        #: count bound on the resident set; 0 = unbounded (the byte
        #: bound is the membudget "device" label cap, set separately)
        self.max_resident = int(max_resident)
        self._lock = make_rlock("tenancy.manager")
        self._tenants: dict[str, _Tenant] = {}
        self._flights: dict[str, _Flight] = {}
        self._seq = 0
        #: recent cold-start walls (ms): /admin/tenants reads its p50
        #: and p99 from this; bounded so it never grows
        self.coldstart_ms: deque[float] = deque(maxlen=4096)

    # --- wiring -----------------------------------------------------------

    def configure(self, max_resident: int | None = None) -> None:
        """Live-update knobs (the tenant_hot parm hook)."""
        if max_resident is not None:
            with self._lock:
                self.max_resident = int(max_resident)

    def attach(self, budget=None) -> None:
        """(Re-)register the pressure handler — idempotent via the
        handler key, so server boots after a membudget reset() are
        safe."""
        (budget or g_membudget).add_pressure_handler(
            self._on_pressure, priority=PRESSURE_PRIORITY,
            key="tenancy")

    # --- the hot path -----------------------------------------------------

    def loop_for(self, coll, deadline=None, warm: bool = False):
        """The collection's ResidentLoop, promoting a cold tenant
        first (single-flight). This IS ``engine.get_resident_loop``
        now — the lifecycle the engine used to open-code lives here.
        ``warm`` is start-up's (``SearchHTTPServer._warm_device``): the
        index is handed to the loop with its closed F1 program set
        dispatched once. A request's thread never asks for it."""
        name = getattr(coll, "name", "coll")
        while True:
            stale = False
            with self._lock:
                t = self._tenants.get(name)
                if t is not None and t.coll is not None \
                        and t.coll is not coll:
                    # same name, different Collection OBJECT (deleted
                    # and recreated, or another registry): the record
                    # — and any live loop — belong to the OLD object;
                    # serving from it would alias tenants
                    stale = True
                elif t is not None and not t.parked \
                        and t.loop is not None and t.loop.alive:
                    self._seq += 1
                    t.seq = self._seq
                    t.hits += 1
                    g_stats.count("tenancy.hit")
                    return t.loop
                else:
                    fl = self._flights.get(name)
                    if fl is None:
                        fl = self._flights[name] = _Flight()
                        leader = True
                    else:
                        leader = False
            if stale:
                g_stats.count("tenancy.stale_record")
                self.release(name)  # outside the lock: park joins
                continue
            if leader:
                return self._promote(name, coll, fl, warm)
            loop = self._ride(name, fl, deadline)
            if loop is not None:
                return loop
            # leader failed without a result (or the loop died between
            # flights): retake the fast path / a fresh flight

    def _ride(self, name: str, fl: _Flight, deadline):
        """Wait out another thread's cold start under OUR deadline —
        an expired rider sheds instead of queueing blind behind a
        build it can no longer use."""
        g_stats.count("tenancy.singleflight_join")
        budget = deadline_mod.Deadline.after(COLD_WAIT_S)
        if deadline is not None and deadline.at < budget.at:
            budget = deadline
        while not fl.ev.is_set():
            left = budget.remaining()
            if left <= 0:
                if deadline is not None and deadline.expired():
                    g_stats.count("tenancy.rider_shed")
                    raise deadline_mod.DeadlineExceeded(
                        f"deadline exceeded waiting for cold start "
                        f"of {name!r}")
                raise TimeoutError(
                    f"cold start of {name!r} timed out")
            fl.ev.wait(min(left, 0.5))
        if fl.err is not None:
            raise fl.err
        return fl.loop

    def _promote(self, name: str, coll, fl: _Flight,
                 warm: bool = False):
        """The leader's cold start: build (or delta-refresh) the
        device base, spawn the loop, account the bytes, evict LRU
        tenants past the hot-set bounds."""
        from ..query import engine
        from ..query.resident import ResidentLoop
        t0 = time.perf_counter()
        try:
            di = engine.get_device_index(coll)
            if warm:
                # start-up hands the loop an index whose closed F1
                # program set has been dispatched once. (A base rebuilt
                # later in the background is warmed before its swap,
                # engine.get_device_index; one rebuilt in place, where
                # two sets do not fit, compiles what its queries hit:
                # warming it on the loop's thread would hold every
                # request behind the whole set.)
                di.warm_f1()
            loop = ResidentLoop(
                lambda: engine.get_device_index(coll),
                gen_fn=lambda: coll.posdb.version,
                name=name)
            coll._resident_loop = loop  # back-compat introspection
            # the HBM ledger (when on) is the source of truth behind
            # the membudget "device" label — it saw every column the
            # refresh registered; resident_bytes() is the always-on
            # fallback computing the same sum from shapes
            nbytes = int(devwatch.collection_bytes(name)
                         or di.resident_bytes())
            with self._lock:
                t = self._tenants.get(name)
                if t is None:
                    t = self._tenants[name] = _Tenant(name, coll)
                self._seq += 1
                t.coll = coll
                t.loop = loop
                t.parked = False
                t.seq = self._seq
                t.nbytes = nbytes
                t.cold_starts += 1
                t.promoted_at = time.time()
            t1 = time.perf_counter()
            self.coldstart_ms.append((t1 - t0) * 1000.0)
            g_stats.count("tenancy.coldstart")
            # trace.record feeds g_stats AND the caller's waterfall —
            # a rider-visible cold start must show up in the trace
            trace_mod.record("tenancy.coldstart", t0, t1, tenant=name)
            fl.loop = loop
            fl.ev.set()
            # OUTSIDE self._lock: the gauge can breach the device cap,
            # whose relief re-enters park() on this manager
            g_membudget.set_gauge("device", f"di:{name}", nbytes)
            self._evict_over_count(keep=name)
            return loop
        except BaseException as exc:
            fl.err = exc
            fl.ev.set()
            raise
        finally:
            with self._lock:
                self._flights.pop(name, None)

    # --- eviction / parking ----------------------------------------------

    def _evict_over_count(self, keep: str | None = None) -> None:
        """LRU-evict unpinned tenants past ``max_resident`` (the byte
        bound rides the membudget device cap instead)."""
        while True:
            with self._lock:
                if self.max_resident <= 0:
                    return
                resident = [t for t in self._tenants.values()
                            if not t.parked]
                if len(resident) <= self.max_resident:
                    return
                victims = [t for t in resident
                           if not t.pinned and t.name != keep]
                if not victims:
                    return
                victim = min(victims, key=lambda t: t.seq).name
            g_stats.count("tenancy.evict")
            self.park(victim)

    def park(self, name: str) -> int:
        """Demote to the cheap parked state: loop stopped, device
        buffers dropped (the jax arrays die with the DeviceIndex),
        host-side packed columns retained on disk by the devindex base
        cache so the next cold start skips the repack. Returns the
        freed device bytes."""
        with self._lock:
            t = self._tenants.get(name)
            if t is None or t.parked:
                return 0
            loop, freed = t.loop, t.nbytes
            t.loop = None
            t.parked = True
            t.nbytes = 0
        if loop is not None:
            loop.stop()
        coll = t.coll
        if coll is not None:
            coll._resident_loop = None
            coll._device_index = None  # device arrays GC → HBM freed
        g_membudget.set_gauge("device", f"di:{name}", 0)
        devwatch.drop(name)  # every plane: columns die with the index
        g_stats.count("tenancy.park")
        log.info("parked tenant %s (%d MB device)", name, freed >> 20)
        return freed

    def _on_pressure(self, need: int) -> int:
        """Membudget pressure: shed cold (least-recent, unpinned)
        tenants before anyone refuses work — or flushes a cache."""
        freed = 0
        while freed < int(need):
            with self._lock:
                victims = [t for t in self._tenants.values()
                           if not t.parked and not t.pinned
                           and t.loop is not None]
                if len(victims) > 1:
                    # spare the hottest tenant — parking the one most
                    # likely mid-request trades a shed for a failed
                    # query (and re-promotes next hit anyway)
                    victims.remove(max(victims, key=lambda t: t.seq))
                if not victims:
                    break
                victim = min(victims, key=lambda t: t.seq).name
            g_stats.count("tenancy.pressure_evict")
            got = self.park(victim)
            if got <= 0:
                break
            freed += got
        return freed

    def pin(self, name: str) -> None:
        """Exempt from eviction (never from release())."""
        with self._lock:
            t = self._tenants.get(name)
            if t is not None:
                t.pinned = True

    def unpin(self, name: str) -> None:
        with self._lock:
            t = self._tenants.get(name)
            if t is not None:
                t.pinned = False

    # --- teardown ---------------------------------------------------------

    def release(self, name: str) -> bool:
        """Full teardown for a DELETED collection (crawlbot delColl /
        the delete lifecycle fix): stop the loop, drop device buffers
        and the gauge, forget the tenant. Unlike park(), pinning does
        not protect — the collection is gone."""
        self.park(name)
        with self._lock:
            return self._tenants.pop(name, None) is not None

    def stop_all(self) -> None:
        """Server shutdown: park everything (records survive, so a
        start()/stop() cycle cold-starts cleanly)."""
        with self._lock:
            names = list(self._tenants)
        for n in names:
            self.park(n)

    def reset(self) -> None:
        """Test isolation: stop loops, drop all records and knobs."""
        self.stop_all()
        with self._lock:
            self._tenants.clear()
            self._flights.clear()
            self.max_resident = 0
            self.coldstart_ms.clear()

    # --- observability ----------------------------------------------------

    def resident_names(self) -> list[str]:
        with self._lock:
            return sorted(t.name for t in self._tenants.values()
                          if not t.parked)

    def snapshot(self) -> dict:
        cs = list(self.coldstart_ms)
        cs.sort()

        def pct(p: float) -> float:
            return round(cs[min(int(p * len(cs)), len(cs) - 1)], 3) \
                if cs else 0.0

        with self._lock:
            tenants = {
                t.name: {
                    "resident": not t.parked,
                    "pinned": t.pinned,
                    "device_bytes": t.nbytes,
                    "hits": t.hits,
                    "cold_starts": t.cold_starts,
                    "lru_seq": t.seq,
                } for t in self._tenants.values()}
            return {
                "max_resident": self.max_resident,
                "resident": sum(1 for t in self._tenants.values()
                                if not t.parked),
                "parked": sum(1 for t in self._tenants.values()
                              if t.parked),
                "device_cap": g_membudget.label_cap("device"),
                "device_bytes": g_membudget.used("device"),
                "coldstart_p50_ms": pct(0.50),
                "coldstart_p99_ms": pct(0.99),
                "coldstarts": len(cs),
                "tenants": tenants,
            }


#: process-wide singleton (the g_collectiondb analog for residency);
#: engine.get_resident_loop routes through it, SearchHTTPServer wires
#: its knobs from the parms and attach()es the pressure handler
g_residency = ResidencyManager()
