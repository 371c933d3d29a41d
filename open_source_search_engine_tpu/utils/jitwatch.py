"""jitwatch — runtime compile / retrace / host-transfer attribution.

The static half of the jit plane (the ``jit-*`` osselint family) bans
the trace-discipline hazards it can see in the AST; this module is the
runtime half: it watches what JAX actually *does* and attributes every
compile, retrace and host transfer to a ``(function, shape-signature,
call-site)`` key, so a steady-state latency cliff (the Gigablast
analog: a Msg39 spike when a query shape misses every warm plan) names
the line that caused it instead of showing up as anonymous tail
latency.

Capture channels (all restored exactly on :func:`disable`):

* ``jax._src.interpreters.partial_eval``'s ``TRACING CACHE MISS at
  <file>:<line>:<col> (<caller>):`` explanations (gated on the
  ``jax_explain_cache_misses`` config, flipped on while enabled) —
  these carry the jit call site and, on the following lines, either
  ``never seen function: <fn> ...`` (a cold first trace) or ``for
  <fn> defined at ...`` plus what differed (a genuine retrace).
* ``jax._src.interpreters.pxla``'s ``Compiling <fn> with global shapes
  and types (...)`` records — emitted at DEBUG even when
  ``jax_log_compiles`` is off, so a DEBUG-level handler sees every
  backend compile without changing global logging behavior.
* ``jax._src.dispatch``'s ``Finished tracing + transforming`` and
  ``Finished XLA compilation of <fn> in <s> sec`` records — per-trace
  and per-compile durations (``jit.trace_ms`` / ``jit.compile_ms``).
* Wrappers around ``jax.device_put`` / ``jax.device_get`` — the
  explicit transfer guard. JAX's own ``transfer_guard("log")`` writes
  from C++ straight to stderr where Python cannot observe it, so the
  blessed transfer entry points are wrapped instead, plus a
  best-effort ``__array__`` patch that catches explicit
  ``device_x.__array__()`` materialization.

Counters feed ``g_stats`` (``jit.compiles``, ``jit.retrace.<site>``,
``jit.transfer.<site>``) and each event drops a zero-width span into
the tracing plane so a sampled trace shows *where inside the request*
the compile landed. ``OSSE_JITWATCH=1`` turns the watcher on via
:func:`maybe_enable` (wired into the device layer import and the
server); with the variable unset this module is inert — importing it
touches neither jax config nor any logger.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

from . import trace
from .stats import g_stats

#: loggers whose records carry the compile/retrace story
_JAX_LOGGERS = ("jax._src.interpreters.partial_eval",
                "jax._src.interpreters.pxla", "jax._src.dispatch")

#: repo-relative module suffixes that OWN device↔host traffic — a
#: transfer attributed elsewhere is a hot-path violation (mirrors
#: osselint's _JIT_TRANSFER_BOUNDARY)
BOUNDARY_SITES = ("query/devindex.py", "query/scorer.py",
                  "parallel/sharded.py", "build/devbuild.py")

_PKG_ROOT = Path(__file__).resolve().parent.parent
_SELF_FILE = str(Path(__file__).resolve())

_MISS_RE = re.compile(
    r"TRACING CACHE MISS at (\S+?):(\d+):\d+ \([^)]*\):\s*(.*)",
    re.DOTALL)
#: the jitted function's name inside a miss explanation: a cold trace
#: says "never seen function:\n  <fn> id=...", a retrace "for <fn>
#: defined at ..."
_MISS_FN_RE = re.compile(
    r"(?:never seen function:\s*|for )(\S+) (?:id=|defined at)")
_COMPILE_RE = re.compile(
    r"Compiling ([^\s]+) with global shapes and types (.*?)\. "
    r"Argument mapping", re.DOTALL)
_TRACED_RE = re.compile(
    r"Finished tracing \+ transforming (\S+) for pjit in "
    r"([0-9.eE+-]+) sec")
_COMPILED_RE = re.compile(
    r"Finished XLA compilation of (\S+) in ([0-9.eE+-]+) sec")


@dataclass
class Event:
    """One attributed compile/retrace/transfer bucket."""
    kind: str            # compile | first_trace | retrace | transfer
    fn: str              # jitted function (or transfer entry point)
    shapes: str          # shape signature ("" when unknown)
    site: str            # file.py:line, repo-relative when possible
    count: int = 0
    bytes: int = 0       # transfers only
    last: str = ""       # last explanation / direction

    def as_dict(self) -> dict:
        return {"kind": self.kind, "fn": self.fn,
                "shapes": self.shapes, "site": self.site,
                "count": self.count, "bytes": self.bytes,
                "boundary": is_boundary_site(self.site),
                "last": self.last}


def is_boundary_site(site: str) -> bool:
    """Does ``site`` live in a module blessed to touch the host?"""
    path = site.rsplit(":", 1)[0]
    return path.endswith(BOUNDARY_SITES)


def _norm_site(filename: str, lineno: int) -> str:
    try:
        rel = Path(filename).resolve().relative_to(_PKG_ROOT)
        return f"{rel.as_posix()}:{lineno}"
    except ValueError:
        return f"{Path(filename).name}:{lineno}"


def _caller_site() -> str:
    """First stack frame outside jitwatch, jax and the stdlib — the
    repo line that triggered the event."""
    for fr in reversed(traceback.extract_stack()):
        fn = fr.filename
        if fn == _SELF_FILE or "site-packages" in fn \
                or "/lib/python" in fn or fn.startswith("<"):
            continue
        return _norm_site(fn, fr.lineno or 0)
    return "unknown:0"


def _nbytes(x) -> int:
    try:
        import jax
        return int(sum(getattr(leaf, "nbytes", 0) or 0
                       for leaf in jax.tree_util.tree_leaves(x)))
    except Exception:
        g_stats.count("jit.nbytes_errors")
        return 0


class _Handler(logging.Handler):
    def __init__(self, watch: "JitWatch"):
        super().__init__(level=logging.DEBUG)
        self._watch = watch

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self._watch._on_record(record)
        except Exception:
            # a broken parse must never take down the jit under watch
            g_stats.count("jit.watch_errors")


class JitWatch:
    """Singleton attribution table; enable()/disable() are idempotent
    and restore every hook they install."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.enabled = False
        self.events: dict[tuple, Event] = {}
        self.totals = {"compiles": 0, "compile_s": 0.0,
                       "first_traces": 0, "retraces": 0,
                       "transfers": 0, "transfers_offboundary": 0}
        self._handler = _Handler(self)
        self._saved_loggers: dict[str, tuple[int, bool]] = {}
        self._saved_explain: bool | None = None
        self._orig_put = None
        self._orig_get = None
        self._orig_array = None
        self._array_cls = None
        self._tl = threading.local()

    # -- lifecycle ----------------------------------------------------

    def enable(self) -> None:
        with self._lock:
            if self.enabled:
                return
            import jax
            self._saved_explain = bool(
                jax.config.jax_explain_cache_misses)
            jax.config.update("jax_explain_cache_misses", True)
            for name in _JAX_LOGGERS:
                lg = logging.getLogger(name)
                self._saved_loggers[name] = (lg.level, lg.propagate)
                lg.setLevel(logging.DEBUG)
                lg.addHandler(self._handler)
                # keep the DEBUG firehose out of the app log while we
                # watch; restored on disable
                lg.propagate = False
            self._orig_put, self._orig_get = (jax.device_put,
                                              jax.device_get)
            orig_put, orig_get = self._orig_put, self._orig_get

            def device_put(*args, **kwargs):
                self._note_transfer("device_put", "h2d", args)
                self._tl.explicit = True
                try:
                    return orig_put(*args, **kwargs)
                finally:
                    self._tl.explicit = False

            def device_get(*args, **kwargs):
                self._note_transfer("device_get", "d2h", args)
                self._tl.explicit = True
                try:
                    return orig_get(*args, **kwargs)
                finally:
                    self._tl.explicit = False

            jax.device_put, jax.device_get = device_put, device_get
            self._patch_array()
            self.enabled = True
            g_stats.gauge("jit.watch_enabled", 1)

    def _patch_array(self) -> None:
        """Best-effort implicit-transfer tripwire for explicit
        ``dev_x.__array__()`` calls. ``np.array``/``np.asarray`` reach
        the data through C-level slots a class-attribute patch cannot
        see — which is exactly why the jit-implicit-transfer static
        rule exists for those spellings."""
        try:
            from jax._src.array import ArrayImpl
            orig = ArrayImpl.__array__

            def patched(arr, *a, **k):
                if not getattr(self._tl, "explicit", False):
                    self._note_transfer("__array__", "d2h-implicit",
                                        arr)
                return orig(arr, *a, **k)

            ArrayImpl.__array__ = patched
            self._array_cls, self._orig_array = ArrayImpl, orig
        except Exception:
            g_stats.count("jit.array_patch_failed")

    def disable(self) -> None:
        with self._lock:
            if not self.enabled:
                return
            import jax
            jax.config.update("jax_explain_cache_misses",
                              self._saved_explain)
            for name, (level, prop) in self._saved_loggers.items():
                lg = logging.getLogger(name)
                lg.removeHandler(self._handler)
                lg.setLevel(level)
                lg.propagate = prop
            self._saved_loggers.clear()
            jax.device_put, jax.device_get = (self._orig_put,
                                              self._orig_get)
            if self._array_cls is not None:
                self._array_cls.__array__ = self._orig_array
                self._array_cls = self._orig_array = None
            self.enabled = False
            g_stats.gauge("jit.watch_enabled", 0)

    def reset(self) -> None:
        """Drop the attribution table (counters in g_stats persist —
        the bench snapshots deltas instead)."""
        with self._lock:
            self.events.clear()
            for k in self.totals:
                self.totals[k] = type(self.totals[k])()

    # -- event plumbing ----------------------------------------------

    def _bump(self, kind: str, fn: str, shapes: str, site: str,
              nbytes: int = 0, last: str = "") -> Event:
        key = (kind, fn, shapes, site)
        with self._lock:
            ev = self.events.get(key)
            if ev is None:
                ev = self.events[key] = Event(kind, fn, shapes, site)
            ev.count += 1
            ev.bytes += nbytes
            if last:
                ev.last = last[:400]
        return ev

    def _on_record(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        m = _COMPILE_RE.search(msg)
        if m:
            now = time.perf_counter()
            site = _caller_site()
            self._bump("compile", m.group(1), m.group(2)[:200], site)
            with self._lock:
                self.totals["compiles"] += 1
            g_stats.count("jit.compiles")
            trace.record("jit.compile", now, now, fn=m.group(1),
                         site=site)
            return
        m = _MISS_RE.search(msg)
        if m:
            now = time.perf_counter()
            site = _norm_site(m.group(1), int(m.group(2)))
            # keep the explanation ("different input types: ..."),
            # drop the MISS header
            why = m.group(3).strip()
            mf = _MISS_FN_RE.search(why)
            fn = mf.group(1) if mf else "unknown"
            if why.startswith("never seen function"):
                self._bump("first_trace", fn, "", site, last=why)
                with self._lock:
                    self.totals["first_traces"] += 1
                g_stats.count("jit.first_traces")
            else:
                self._bump("retrace", fn, "", site, last=why)
                with self._lock:
                    self.totals["retraces"] += 1
                g_stats.count("jit.retraces")
                g_stats.count(f"jit.retrace.{site}")
                trace.record("jit.retrace", now, now, fn=fn,
                             site=site)
            return
        m = _TRACED_RE.search(msg)
        if m:
            g_stats.record_ms("jit.trace_ms",
                              1000.0 * float(m.group(2)))
            return
        m = _COMPILED_RE.search(msg)
        if m:
            secs = float(m.group(2))
            with self._lock:
                self.totals["compile_s"] += secs
            g_stats.record_ms("jit.compile_ms", 1000.0 * secs)

    def _note_transfer(self, fn: str, direction: str, args) -> None:
        now = time.perf_counter()
        site = _caller_site()
        nbytes = _nbytes(args)
        self._bump("transfer", fn, "", site, nbytes=nbytes,
                   last=direction)
        offb = not is_boundary_site(site)
        with self._lock:
            self.totals["transfers"] += 1
            if offb:
                self.totals["transfers_offboundary"] += 1
        g_stats.count("jit.transfers")
        g_stats.count(f"jit.transfer.{site}")
        trace.record("jit.transfer", now, now, fn=fn, site=site,
                     direction=direction, bytes=nbytes)

    # -- reporting ----------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            events = sorted(self.events.values(),
                            key=lambda e: -e.count)
            return {"enabled": self.enabled,
                    "totals": dict(self.totals),
                    "events": [e.as_dict() for e in events]}


g_jitwatch = JitWatch()


def enable() -> None:
    g_jitwatch.enable()


def disable() -> None:
    g_jitwatch.disable()


def enabled() -> bool:
    return g_jitwatch.enabled


def reset() -> None:
    g_jitwatch.reset()


def snapshot() -> dict:
    return g_jitwatch.snapshot()


def maybe_enable() -> None:
    """Enable iff OSSE_JITWATCH=1 — the import-time wiring used by the
    device layer and the server; a true no-op otherwise."""
    if os.environ.get("OSSE_JITWATCH", "") == "1":
        enable()
