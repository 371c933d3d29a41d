"""Named daemon-thread helpers — the one sanctioned way to spawn.

Reference: the gb binary had no anonymous threads — every worker was a
named loop registered with the Loop/BigFile thread queues, so a hung
process could always be diagnosed from a thread dump. Our reproduction
had drifted into a dozen ad-hoc ``threading.Thread(...)`` call sites,
some named, some not (an unnamed thread in a py-spy dump is a dead
end). Every spawn now flows through here; the ``thread-spawn`` osselint
rule keeps it that way.

All helper threads are daemons: background workers (SWR refreshes,
heartbeats, samplers) must never block interpreter exit — orderly
shutdown is the job of each owner's ``stop()``, not of ``join`` at
teardown.

A thread's name is also its **role** in the process's CPU account
(:func:`role_of`, :class:`CpuAccount`): the counters
``host.cpu_us.<role>`` say whose CPU the host's second went to.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

from . import schedcheck as _schedcheck
from .stats import g_stats

#: the roles of the CPU account, each a counter ``host.cpu_us.<role>``
ROLES = ("loop", "batcher", "pool", "front", "other")
#: roles whose threads live as long as the server and are read from
#: their own CPU clocks when ``g_stats`` takes a snapshot
_POLLED = frozenset(("loop", "batcher", "pool"))


def make_thread(name: str, target: Callable[..., Any], *args: Any,
                **kwargs: Any) -> threading.Thread:
    """A named daemon thread, NOT started (callers that must publish
    the Thread object before it runs — batch workers whose loop checks
    ``self._thread``). Inside an active ``schedcheck.explore`` the
    thread is a cooperatively scheduled one."""
    if _schedcheck._active is not None:
        sched = _schedcheck.maybe_thread(name, target, args, kwargs)
        if sched is not None:
            return sched
    return threading.Thread(target=target, args=args, kwargs=kwargs,
                            daemon=True, name=name)


def spawn(name: str, target: Callable[..., Any], *args: Any,
          **kwargs: Any) -> threading.Thread:
    """Create AND start a named daemon thread; returns it for joining."""
    t = make_thread(name, target, *args, **kwargs)
    t.start()
    return t


def role_of(name: str) -> str:
    """A thread's role, from its name: the resident loop
    (``resident-loop-*``), the batcher (``query-batcher``), its pool
    (``query-pool*``), an HTTP handler (the standard library names it
    ``Thread-N (process_request_thread)``), else ``other``."""
    if name.startswith("resident-loop-"):
        return "loop"
    if name == "query-batcher":
        return "batcher"
    if name.startswith("query-pool"):
        return "pool"
    if name.endswith("(process_request_thread)"):
        return "front"
    return "other"


def _thread_cpu(t: threading.Thread) -> float | None:
    """A live thread's CPU seconds from its own clock, or None."""
    ident = t.ident
    if ident is None or not t.is_alive():
        return None
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(ident))
    except OSError:                     # ended since it was listed
        return None


class CpuAccount:
    """The process's CPU time by thread role, published as the counters
    ``host.cpu_us.<role>`` (µs) whenever ``g_stats`` takes a snapshot.

    * ``loop``, ``batcher``, ``pool``: read from each thread's own CPU
      clock at the snapshot; nothing runs on their hot paths. The CPU a
      thread spends between its last reading and its end falls to
      ``other``.
    * ``front``: an HTTP handler lives one connection, so it adds its
      whole CPU once, at its end (:meth:`add_front`).
    * ``other``: the process's CPU clock less the four above — the
      main thread, ``httpd-*``, ``statsdb``, threads the runtime and
      the profiler start outside Python, and a handler's CPU until it
      ends (then it moves to ``front``; ``other`` never goes back).

    Memory is bounded: the totals, and the last reading of each live
    polled thread (a thread that has ended is forgotten)."""

    def __init__(self):
        self._lock = threading.Lock()
        #: CPU seconds by role since the process started
        self._total = dict.fromkeys(ROLES, 0.0)
        #: µs by role already published to ``g_stats``
        self._published = dict.fromkeys(ROLES, 0)
        #: the last reading of each live polled thread
        self._last: dict[threading.Thread, float] = {}

    def add_front(self) -> None:
        """Add the calling handler thread's whole CPU, at its end."""
        cpu = time.thread_time()
        with self._lock:
            self._total["front"] += cpu

    def poll(self) -> dict[str, int]:
        """Read the polled threads and the process clock; the µs each
        role gained since the last poll, as counter increments."""
        with self._lock:
            last = {}
            for t in threading.enumerate():
                role = role_of(t.name)
                if role not in _POLLED:
                    continue
                cpu = _thread_cpu(t)
                if cpu is None:
                    continue
                self._total[role] += cpu - self._last.get(t, 0.0)
                last[t] = cpu
            self._last = last
            named = sum(v for r, v in self._total.items() if r != "other")
            self._total["other"] = max(self._total["other"],
                                       time.process_time() - named)
            out = {}
            for role, sec in self._total.items():
                us = int(sec * 1e6) - self._published[role]
                if us > 0:
                    self._published[role] += us
                    out[f"host.cpu_us.{role}"] = us
            return out


#: the process's one CPU account, folded into every ``g_stats`` snapshot
g_cpu = CpuAccount()
g_stats.add_poll(g_cpu.poll)
