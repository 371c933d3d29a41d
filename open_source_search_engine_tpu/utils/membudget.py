"""Host-plane memory-budget governor — the Mem.cpp allocation gate.

Reference: the single ``gb`` binary enforces ``Conf::m_maxMem`` through
``g_mem`` (``Mem.cpp:255``): every large allocation registers with a
label, over-budget requests are REFUSED, and the caller degrades
(defer the merge, dump the tree, shed the batch) instead of letting
the kernel OOM-kill the process. This is that plane for the host side
of the TPU port: one process-wide :class:`MemBudget` (``g_membudget``)
keyed off the existing ``max_mem`` parm.

Two accounting styles, both counted against the one limit:

* **reservations** (``reserve``/``release``) — transient working sets
  with a clear lifetime: a merge's input+output arrays, a pack pass's
  padded device staging arrays, a build batch's concatenated key
  images. ``reserving()`` is the context-manager form.
* **gauges** (``set_gauge``) — long-lived structures that grow and
  shrink in place, keyed per owner: each Rdb reports its memtable
  bytes under the ``memtable`` label and the governor sums them.

On an over-budget ``reserve`` the governor first runs registered
**pressure handlers** (flush-the-memtable hooks — weakly referenced so
a dead Collection never pins memory or leaks handlers), re-checks, and
only then refuses. Every refusal bumps ``membudget.reject.<label>`` in
``g_stats`` (statsdb surfaces it) and the caller is expected to shrink
or defer — never to crash.

The device plane's twin is ``query/devcheck.py`` (checkify harness);
``/admin/mem`` serves :meth:`MemBudget.snapshot` live.
"""

from __future__ import annotations

import threading
import weakref
from collections import deque
from typing import Callable

from .log import get_logger
from .stats import g_stats

log = get_logger("membudget")

#: default budget — the ``max_mem`` parm default (4 GB/instance,
#: Conf::m_maxMem); serve wiring overwrites it from the live conf
DEFAULT_LIMIT = 4 << 30

#: the per-subsystem labels the core planes report under (free-form
#: strings are accepted; these are the wired ones). "device" is the
#: tenant plane's HBM-resident index bytes (serve/tenancy.py).
LABELS = ("memtable", "merge", "pack", "docproc", "cache", "device")


class MemBudget:
    """Process-wide labeled memory budget with graceful refusal."""

    def __init__(self, limit: int = DEFAULT_LIMIT):
        self._lock = threading.Lock()
        self.limit = int(limit)
        #: label -> reserved bytes (transient working sets)
        self._reserved: dict[str, int] = {}
        #: label -> {owner key -> bytes} (long-lived gauges)
        self._gauges: dict[str, dict[object, int]] = {}
        #: label -> soft cap in bytes (set_label_cap); breaching a cap
        #: runs the pressure pass scoped to that label rather than
        #: refusing — the "device" cap bounds the resident tenant set
        #: independently of the global limit
        self._caps: dict[str, int] = {}
        #: label -> refusal count (mirrors the g_stats counters)
        self.rejections: dict[str, int] = {}
        self.high_water = 0
        #: (priority, seq, key, weak fn) — run ascending by priority
        #: until the budget fits, so cheap shedders (cold tenants) go
        #: before expensive ones (the cache plane)
        self._pressure: list[tuple] = []
        self._pressure_seq = 0
        #: labels with a cap-relief pass in flight (a handler that
        #: zeroes gauges re-enters set_gauge; the guard stops the
        #: recursion, not the relief)
        self._relieving: set[str] = set()
        #: (label, key) gauges whose owner was garbage-collected: a
        #: finalizer may run on a thread that HOLDS ``_lock`` (the
        #: collector strikes at any allocation, also inside
        #: ``_used_locked``), so it only appends here and the next
        #: locked reader drops them
        self._dead: deque = deque()

    # --- limit -----------------------------------------------------------

    def set_limit(self, limit: int) -> None:
        """Re-point the budget (the max_mem parm live-update hook)."""
        with self._lock:
            self.limit = max(int(limit), 1)

    def set_label_cap(self, label: str, nbytes: int) -> None:
        """Soft cap for ONE label, independent of the global limit
        (0/negative clears). Breaching it triggers a label-scoped
        pressure pass (``membudget.cap_evict``) instead of a refusal —
        the device label's cap is how the tenant plane sizes its hot
        set."""
        with self._lock:
            if int(nbytes) <= 0:
                self._caps.pop(label, None)
                return
            self._caps[label] = int(nbytes)
        g_stats.gauge(f"membudget.cap.{label}", int(nbytes))

    def label_cap(self, label: str) -> int:
        """The label's soft cap, 0 = uncapped."""
        with self._lock:
            return self._caps.get(label, 0)

    # --- accounting ------------------------------------------------------

    def forget_gauge(self, label: str, key: object) -> None:
        """Drop an owner's gauge from its finalizer (``__del__``):
        takes no lock, so it cannot deadlock a thread that the
        collector interrupted while it held the budget's."""
        self._dead.append((label, key))

    def _reap_locked(self) -> None:
        while self._dead:
            label, key = self._dead.popleft()
            self._gauges.get(label, {}).pop(key, None)

    def _used_locked(self) -> int:
        self._reap_locked()
        return (sum(self._reserved.values())
                + sum(sum(g.values()) for g in self._gauges.values()))

    def used(self, label: str | None = None) -> int:
        with self._lock:
            if label is None:
                return self._used_locked()
            return self._label_used_locked(label)

    def free(self) -> int:
        with self._lock:
            return max(self.limit - self._used_locked(), 0)

    def would_fit(self, nbytes: int) -> bool:
        with self._lock:
            return self._used_locked() + int(nbytes) <= self.limit

    def set_gauge(self, label: str, key: object, nbytes: int) -> None:
        """Absolute usage of one owner under a label (0 removes it).
        ``key`` is any hashable owner identity (an Rdb's dir path).
        Pushing a capped label over its soft cap runs the label-scoped
        pressure pass (counted ``membudget.cap_evict``)."""
        with self._lock:
            g = self._gauges.setdefault(label, {})
            if nbytes <= 0:
                g.pop(key, None)
            else:
                g[key] = int(nbytes)
            self.high_water = max(self.high_water, self._used_locked())
            cap = self._caps.get(label, 0)
            over = (cap > 0 and label not in self._relieving
                    and self._label_used_locked(label) > cap)
            if over:
                self._relieving.add(label)
        if over:
            try:
                g_stats.count("membudget.cap_evict")
                g_stats.count(f"membudget.cap_evict.{label}")
                with self._lock:
                    excess = self._label_used_locked(label) - cap
                self._relieve(max(excess, 1), label=label)
            finally:
                with self._lock:
                    self._relieving.discard(label)

    def _label_used_locked(self, label: str) -> int:
        self._reap_locked()
        return (self._reserved.get(label, 0)
                + sum(self._gauges.get(label, {}).values()))

    def _label_fits_locked(self, label: str) -> bool:
        cap = self._caps.get(label, 0)
        return cap <= 0 or self._label_used_locked(label) <= cap

    def reserve(self, label: str, nbytes: int) -> bool:
        """Claim ``nbytes`` under ``label``; False = over budget (after
        pressure relief) and the caller must degrade. Zero/negative
        requests always succeed (and claim nothing)."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            return True
        from .chaos import g_chaos
        if g_chaos.enabled and \
                g_chaos.decide("membudget.reserve", key=label):
            # forced pressure: the shed-before-refuse path must run
            # even when the budget would have fit
            self._relieve(nbytes)
        def _fits_locked() -> bool:
            if self._used_locked() + nbytes > self.limit:
                return False
            cap = self._caps.get(label, 0)
            return cap <= 0 or \
                self._label_used_locked(label) + nbytes <= cap

        with self._lock:
            fits = _fits_locked()
            globally = self._used_locked() + nbytes <= self.limit
        if not fits:
            # a label-cap-only breach relieves scoped to the label;
            # a global breach runs the full ladder
            self._relieve(nbytes, label=None if not globally else label)
            with self._lock:
                fits = _fits_locked()
        if not fits:
            with self._lock:
                self.rejections[label] = \
                    self.rejections.get(label, 0) + 1
            g_stats.count("membudget.reject")
            g_stats.count(f"membudget.reject.{label}")
            log.warning(
                "over budget: %s wants %d MB, %d MB free of %d MB — "
                "refusing (caller degrades)", label, nbytes >> 20,
                self.free() >> 20, self.limit >> 20)
            return False
        with self._lock:
            self._reserved[label] = \
                self._reserved.get(label, 0) + nbytes
            self.high_water = max(self.high_water, self._used_locked())
        return True

    def release(self, label: str, nbytes: int) -> None:
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._lock:
            cur = self._reserved.get(label, 0)
            self._reserved[label] = max(cur - nbytes, 0)

    class _Reservation:
        def __init__(self, budget: "MemBudget", label: str, n: int):
            self.budget, self.label, self.n = budget, label, n
            self.granted = False

        def __enter__(self):
            self.granted = self.budget.reserve(self.label, self.n)
            return self.granted

        def __exit__(self, *exc):
            if self.granted:
                self.budget.release(self.label, self.n)
            return False

    def reserving(self, label: str, nbytes: int) -> "_Reservation":
        """``with g_membudget.reserving("merge", est) as ok:`` —
        releases on exit when granted; ``ok`` is the grant."""
        return MemBudget._Reservation(self, label, int(nbytes))

    # --- pressure relief -------------------------------------------------

    def add_pressure_handler(
            self, fn: Callable[[int], int], priority: int = 100,
            key: str | None = None) -> None:
        """Register a memory-freeing hook run before a refusal:
        ``fn(need_bytes) -> freed_bytes_hint``. Bound methods are held
        through ``weakref.WeakMethod`` so registering never pins the
        owner (a test's ShardedCollection must be collectable).

        Handlers run in ascending ``priority`` order and the pass stops
        as soon as the budget fits — the tenant plane registers at a
        LOW priority so device pressure sheds cold tenants before the
        cache plane flushes anything. ``key`` makes registration
        idempotent (re-adding the same key replaces the old entry —
        singletons re-attach safely after a ``reset()``)."""
        with self._lock:
            try:
                ref: object = weakref.WeakMethod(fn)  # bound method
            except TypeError:
                ref = weakref.ref(fn) if hasattr(fn, "__name__") \
                    else (lambda: fn)
            if key is not None:
                self._pressure = [e for e in self._pressure
                                  if e[2] != key]
            self._pressure_seq += 1
            self._pressure.append(
                (int(priority), self._pressure_seq, key, ref))

    def _relieve(self, need: int, label: str | None = None) -> None:
        """The shed pass: handlers ascending by priority, stopping the
        moment the budget (or, for a cap breach, the label) fits —
        cheap shedders spare expensive ones. At least one handler
        always runs (chaos-forced pressure exercises the pass even
        when the reservation would fit)."""
        with self._lock:
            entries = sorted(self._pressure, key=lambda e: (e[0], e[1]))
        dead = []
        ran = 0
        for entry in entries:
            fn = entry[3]()
            if fn is None:
                dead.append(entry)  # owner collected: drop the handler
                continue
            try:
                fn(need)
            except Exception as e:  # noqa: BLE001 — relief best-effort
                log.warning("pressure handler failed: %s", e)
            ran += 1
            with self._lock:
                fits = self._label_fits_locked(label) if label \
                    else self._used_locked() + need <= self.limit
            if fits:
                break
        if dead:
            with self._lock:
                self._pressure = [e for e in self._pressure
                                  if e not in dead]

    # --- introspection (/admin/mem) -------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            self._reap_locked()
            labels: dict[str, dict] = {}
            for lb in sorted(set(self._reserved)
                             | set(self._gauges)
                             | set(self.rejections) | set(LABELS)):
                labels[lb] = {
                    "reserved": self._reserved.get(lb, 0),
                    "gauged": sum(
                        self._gauges.get(lb, {}).values()),
                    "rejections": self.rejections.get(lb, 0),
                    "cap": self._caps.get(lb, 0),
                }
            used = self._used_locked()
            return {
                "limit": self.limit,
                "used": used,
                "free": max(self.limit - used, 0),
                "high_water": self.high_water,
                "rejections": sum(self.rejections.values()),
                "labels": labels,
            }

    def reset(self) -> None:
        """Drop all accounting (test isolation; the limit stays)."""
        with self._lock:
            self._reserved.clear()
            self._gauges.clear()
            self._caps.clear()
            self.rejections.clear()
            self.high_water = 0
            self._pressure = []
            self._relieving.clear()
            self._dead.clear()


#: process-wide singleton (reference ``g_mem``)
g_membudget = MemBudget()
