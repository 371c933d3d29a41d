"""devwatch — the device telemetry plane: HBM ledger, wave flight
recorder, roofline attribution.

jitwatch (the runtime half of the jit plane) watches what JAX *does* —
compiles, retraces, host transfers. This module watches what the
device *holds* and what the waves *cost*, the layer tracing (host
spans) and fleet metrics (host counters) both stop short of:

* **HBM ledger** — every long-lived ``device_put`` in the device
  layers (devindex columns, devbuild staging, mesh shard staging)
  registers its buffer under a ``(collection, plane, column)`` label.
  The ledger is the number the tenant plane's byte-bounded residency
  reasons about (the membudget "device" label's source of truth when
  enabled), reconciles against ``device.memory_stats()`` where the
  backend exposes it (TPU yes, CPU returns None), and exports
  ``hbm.<plane>.bytes`` gauges so ``/metrics`` can scrape per-plane
  residency fleet-wide.
* **Wave flight recorder** — a bounded ring of per-wave records from
  the resident loop (single-chip DeviceIndex waves and MeshServeIndex
  shard_map waves ride the same hooks): issue→dispatch→collect timing
  split (built from the clock readings of the loop's own spans,
  ``resident.issue_wave`` / ``.collect_wave``, handed to
  :meth:`DevWatch.wave_end` as ``marks``: this module reads no clock
  for a wave), per-round device time and fetched bytes, escalation reissues,
  and the modeled ``wave_bytes_per_query`` next to what the round
  actually moved. Each wave also drops a device-tagged span into the
  trace plane, so a sampled trace shows the wave *inside* the request.
* **Roofline attribution** — at first dispatch of each (kernel, shape
  bucket), pull ``.cost_analysis()`` (flops / bytes accessed) from the
  compiled executable, compute arithmetic intensity, and issue a
  bandwidth-bound / compute-bound verdict against the backend's peak
  numbers. This is the instrument the fused-Pallas footprint items
  use to prove a wave-bytes delta instead of asserting one.

``OSSE_DEVWATCH=1`` turns the plane on via :func:`maybe_enable`
(wired into the device-layer imports and the server, next to
jitwatch); with the variable unset every hook is a guarded early
return — importing this module touches nothing and the hot path pays
one attribute load per call site.
"""

from __future__ import annotations

import os
import threading
from collections import deque

from . import trace
from .stats import g_stats

#: flight-recorder ring bound — old waves fall off, the plane never
#: grows with uptime
RING = int(os.environ.get("OSSE_DEVWATCH_RING", "256"))

#: published peak (dense-matmul FLOP/s, HBM bytes/s) per TPU
#: generation — matched by substring against ``device_kind``. The
#: roofline ridge (flops/bw) splits bandwidth-bound from
#: compute-bound; exact peaks matter less than which side of the
#: ridge a kernel lands on.
_TPU_PEAKS = (
    ("v5 lite", 197e12, 819e9, "tpu-v5e"),
    ("v5e", 197e12, 819e9, "tpu-v5e"),
    ("v5p", 459e12, 2765e9, "tpu-v5p"),
    ("v6", 918e12, 1640e9, "tpu-v6e"),
    ("v4", 275e12, 1228e9, "tpu-v4"),
    ("v3", 123e12, 900e9, "tpu-v3"),
    ("v2", 45e12, 700e9, "tpu-v2"),
)

#: order-of-magnitude host numbers for a CPU run — labeled assumed so
#: nobody reads a CI-box verdict as a chip verdict
_CPU_PEAKS = (2e11, 4e10, "cpu (assumed)")


def peaks_row(device_kind: str) -> tuple:
    """The ``_TPU_PEAKS`` row an accelerator's ``device_kind`` matches.
    A kind that matches no row is an error, never a default: a
    roofline against another chip's (or the host's) peaks is wrong in
    silence."""
    kind = str(device_kind).lower()
    for row in _TPU_PEAKS:
        if row[0] in kind:
            return row
    raise LookupError(
        f"device_kind {device_kind!r} matches no row of "
        "devwatch._TPU_PEAKS — add its published peaks")


def _nbytes(a) -> int:
    """Bytes of one registered buffer — accepts a device array, a
    numpy array, or a plain int."""
    if isinstance(a, int):
        return a
    try:
        return int(a.nbytes)
    except Exception:
        try:
            n = 1
            for s in a.shape:
                n *= int(s)
            return n * a.dtype.itemsize
        except Exception:
            g_stats.count("devwatch.nbytes_errors")
            return 0


class DevWatch:
    """Singleton telemetry plane; enable()/disable() are idempotent
    flag flips — unlike jitwatch there is nothing to patch, every
    capture point is an explicit hook in the device layers."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.enabled = False
        #: (collection, plane) -> {column: bytes}
        self.ledger: dict[tuple[str, str], dict[str, int]] = {}
        self._planes: set[str] = set()
        #: bounded flight-recorder ring
        self.waves: deque = deque(maxlen=RING)
        #: (kernel, bucket-tuple) -> roofline entry
        self.costs: dict[tuple[str, tuple], dict] = {}
        self.totals = {"waves": 0, "wave_errors": 0, "rounds": 0}
        self.wave_seq = 0
        self._peaks: dict | None = None
        self._tl = threading.local()

    # -- lifecycle ----------------------------------------------------

    def enable(self) -> None:
        with self._lock:
            if self.enabled:
                return
            self.enabled = True
        g_stats.gauge("devwatch.enabled", 1)

    def disable(self) -> None:
        with self._lock:
            if not self.enabled:
                return
            self.enabled = False
        g_stats.gauge("devwatch.enabled", 0)

    def reset(self) -> None:
        """Drop ledger, ring and cost table (g_stats counters persist —
        benches snapshot deltas instead)."""
        with self._lock:
            self.ledger.clear()
            self.waves.clear()
            self.costs.clear()
            for k in self.totals:
                self.totals[k] = 0
            self.wave_seq = 0
        self._export_gauges()

    # -- HBM ledger ---------------------------------------------------

    def note_columns(self, coll: str, plane: str, columns: dict) -> None:
        """Register (replace) the whole (collection, plane) slice —
        the device-index refresh path: one call after every rebuild
        covers base, delta and regrow identically."""
        if not self.enabled:
            return
        sizes = {str(k): _nbytes(v) for k, v in columns.items()}
        with self._lock:
            self.ledger[(coll, plane)] = sizes
            self._planes.add(plane)
        self._export_gauges()

    def note_buffer(self, coll: str, plane: str, column: str,
                    nbytes) -> None:
        """Register (replace) ONE buffer — transient staging (mesh
        wave operands, build scratch) that comes and goes per wave."""
        if not self.enabled:
            return
        with self._lock:
            self.ledger.setdefault((coll, plane), {})[column] = \
                _nbytes(nbytes)
            self._planes.add(plane)
        self._export_gauges()

    def drop_buffer(self, coll: str, plane: str, column: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            cols = self.ledger.get((coll, plane))
            if cols is not None:
                cols.pop(column, None)
        self._export_gauges()

    def drop(self, coll: str, plane: str | None = None) -> None:
        """Release a collection's entries (one plane, or all on park /
        delColl)."""
        if not self.enabled:
            return
        with self._lock:
            for key in [k for k in self.ledger
                        if k[0] == coll
                        and (plane is None or k[1] == plane)]:
                del self.ledger[key]
        self._export_gauges()

    def collection_bytes(self, coll: str) -> int:
        with self._lock:
            return sum(sum(cols.values())
                       for (c, _p), cols in self.ledger.items()
                       if c == coll)

    def total_bytes(self) -> int:
        with self._lock:
            return sum(sum(cols.values())
                       for cols in self.ledger.values())

    def _export_gauges(self) -> None:
        with self._lock:
            per_plane = {p: 0 for p in self._planes}
            for (_c, p), cols in self.ledger.items():
                per_plane[p] = per_plane.get(p, 0) + sum(cols.values())
        for p, n in per_plane.items():
            g_stats.gauge(f"hbm.{p}.bytes", n)
        g_stats.gauge("hbm.total.bytes", sum(per_plane.values()))

    def reconcile(self) -> dict:
        """Ledger vs what the runtime says the chip holds.
        ``memory_stats()`` is backend-dependent: TPU reports
        bytes_in_use / peak / limit, CPU returns None — degrade to
        nulls, never raise. Fragmentation is the slice of live device
        bytes the ledger cannot name (allocator slack + unregistered
        temporaries); headroom is limit − in_use."""
        ledger_total = self.total_bytes()
        devices = []
        try:
            import jax
            for d in jax.devices():
                try:
                    ms = d.memory_stats()
                except Exception:
                    ms = None
                ent = {"device": str(d),
                       "kind": getattr(d, "device_kind", "unknown")}
                if ms:
                    in_use = int(ms.get("bytes_in_use", 0))
                    peak = int(ms.get("peak_bytes_in_use", 0))
                    limit = int(ms.get("bytes_limit", 0) or 0)
                    ent.update({
                        "bytes_in_use": in_use,
                        "peak_bytes_in_use": peak,
                        "bytes_limit": limit or None,
                        "headroom": (limit - in_use) if limit else None,
                        "ledger_delta": in_use - ledger_total,
                        "fragmentation": (
                            max(0.0, (in_use - ledger_total) / in_use)
                            if in_use else 0.0)})
                else:
                    ent.update({"bytes_in_use": None,
                                "peak_bytes_in_use": None,
                                "bytes_limit": None, "headroom": None,
                                "ledger_delta": None,
                                "fragmentation": None})
                devices.append(ent)
        except Exception:
            g_stats.count("devwatch.reconcile_errors")
        return {"ledger_bytes": ledger_total, "devices": devices}

    # -- wave flight recorder ----------------------------------------

    def wave_begin(self, source: str, **tags) -> dict | None:
        """Open a wave record on the loop thread, before issue.
        Returns None when disabled — every later stage no-ops on
        None, so call sites never branch."""
        if not self.enabled:
            return None
        with self._lock:
            self.wave_seq += 1
            seq = self.wave_seq
        return {"seq": seq, "source": source, "tags": dict(tags),
                "rounds": []}

    def wave_issued(self, obs: dict | None, **tags) -> None:
        if obs is None:
            return
        obs["tags"].update(tags)

    def wave_collect(self, obs: dict | None) -> None:
        """Collect starts: rounds deposited by the index's
        collect_batch (via :meth:`note_round`, same thread) attach to
        this wave until :meth:`wave_end`."""
        if obs is None:
            return
        self._tl.active = obs

    def note_round(self, **detail) -> None:
        """One collect round (fetch + parse + escalation reissue) as
        seen from inside collect_batch — device time, bytes fetched,
        modeled bytes, escalations. Attaches to the thread's active
        wave; a collect outside the loop (warm, direct search) is
        counted, not recorded."""
        if not self.enabled:
            return
        obs = getattr(self._tl, "active", None)
        if obs is None:
            g_stats.count("devwatch.rounds_unattached")
            return
        obs["rounds"].append(detail)
        with self._lock:
            self.totals["rounds"] += 1

    def wave_end(self, obs: dict | None, marks: tuple,
                 error: str | None = None, **tags) -> None:
        """Close the record. ``marks`` are the caller's four clock
        readings (issue began, issue done, collect began, collect
        done): the same ones its ``resident.*`` spans were made from."""
        if obs is None:
            return
        if getattr(self._tl, "active", None) is obs:
            self._tl.active = None
        obs["tags"].update(tags)
        t0, ti, tc, t_end = marks
        rec = {"seq": obs["seq"], "source": obs["source"],
               "issue_s": ti - t0, "wait_s": max(0.0, tc - ti),
               "collect_s": max(0.0, t_end - tc),
               "total_s": t_end - t0,
               "rounds": obs["rounds"], "error": error}
        rec.update(obs["tags"])
        with self._lock:
            self.waves.append(rec)
            self.totals["waves"] += 1
            if error:
                self.totals["wave_errors"] += 1
        g_stats.count("devwatch.waves")
        g_stats.record_ms("devwatch.wave_ms", 1000.0 * (t_end - t0))
        trace.record("devwatch.wave", t0, t_end, device=1,
                     source=obs["source"], seq=obs["seq"],
                     rounds=len(obs["rounds"]), error=error or "")

    # -- roofline attribution ----------------------------------------

    def _peaks_for(self) -> dict:
        if self._peaks is None:
            import jax
            d0 = jax.devices()[0]
            if d0.platform == "cpu":
                flops, bw, label = _CPU_PEAKS
            else:
                _, flops, bw, label = peaks_row(d0.device_kind)
            self._peaks = {"flops": flops, "bw": bw, "label": label,
                           "assumed": d0.platform == "cpu",
                           "ridge": flops / bw}
        return self._peaks

    def note_cost(self, kernel: str, bucket, thunk,
                  modeled_bytes=None) -> None:
        """Roofline one (kernel, shape-bucket): the FIRST dispatch
        pays one ``lower().compile().cost_analysis()`` via ``thunk``
        (the compile itself is warm — the real dispatch right after
        compiles the same shapes anyway); every later dispatch is a
        dict hit + counter bump, so a watched wave costs what an
        unwatched one does."""
        if not self.enabled:
            return
        key = (kernel, tuple(int(x) for x in bucket))
        ent = self.costs.get(key)
        if ent is not None:
            ent["dispatches"] += 1
            return
        peaks = self._peaks_for()
        flops = nbytes = 0.0
        try:
            ca = thunk().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0) or 0.0)
            nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            g_stats.count("devwatch.cost_errors")
        intensity = (flops / nbytes) if nbytes else 0.0
        verdict = ("unknown" if not nbytes else
                   "bandwidth-bound" if intensity < peaks["ridge"]
                   else "compute-bound")
        entry = {"kernel": kernel, "bucket": list(key[1]),
                 "flops": flops, "bytes": nbytes,
                 "intensity": intensity, "ridge": peaks["ridge"],
                 "verdict": verdict,
                 "modeled_bytes": (int(modeled_bytes)
                                   if modeled_bytes else None),
                 "dispatches": 1, "peak": peaks["label"],
                 "assumed": peaks["assumed"]}
        with self._lock:
            self.costs.setdefault(key, entry)
        g_stats.count("devwatch.cost_entries")

    # -- reporting ----------------------------------------------------

    def ledger_snapshot(self) -> dict:
        """collection → plane → column → bytes."""
        out: dict = {}
        with self._lock:
            for (c, p), cols in self.ledger.items():
                out.setdefault(c, {})[p] = dict(cols)
        return out

    def snapshot(self) -> dict:
        with self._lock:
            waves = list(self.waves)
            costs = sorted(self.costs.values(),
                           key=lambda e: (e["kernel"], e["bucket"]))
            totals = dict(self.totals)
            per_plane: dict[str, int] = {}
            per_coll: dict[str, int] = {}
            for (c, p), cols in self.ledger.items():
                n = sum(cols.values())
                per_plane[p] = per_plane.get(p, 0) + n
                per_coll[c] = per_coll.get(c, 0) + n
        return {"enabled": self.enabled,
                "totals": totals,
                "ledger": self.ledger_snapshot(),
                "planes": per_plane,
                "collections": per_coll,
                "total_bytes": sum(per_plane.values()),
                "reconcile": self.reconcile(),
                "waves": waves,
                "rooflines": costs,
                "peaks": self._peaks_for()}


g_devwatch = DevWatch()


def enable() -> None:
    g_devwatch.enable()


def disable() -> None:
    g_devwatch.disable()


def enabled() -> bool:
    return g_devwatch.enabled


def reset() -> None:
    g_devwatch.reset()


def snapshot() -> dict:
    return g_devwatch.snapshot()


def reconcile() -> dict:
    return g_devwatch.reconcile()


def note_columns(coll: str, plane: str, columns: dict) -> None:
    g_devwatch.note_columns(coll, plane, columns)


def note_buffer(coll: str, plane: str, column: str, nbytes) -> None:
    g_devwatch.note_buffer(coll, plane, column, nbytes)


def drop_buffer(coll: str, plane: str, column: str) -> None:
    g_devwatch.drop_buffer(coll, plane, column)


def drop(coll: str, plane: str | None = None) -> None:
    g_devwatch.drop(coll, plane)


def collection_bytes(coll: str) -> int:
    return g_devwatch.collection_bytes(coll)


def wave_begin(source: str, **tags) -> dict | None:
    return g_devwatch.wave_begin(source, **tags)


def wave_issued(obs, **tags) -> None:
    g_devwatch.wave_issued(obs, **tags)


def wave_collect(obs) -> None:
    g_devwatch.wave_collect(obs)


def note_round(**detail) -> None:
    g_devwatch.note_round(**detail)


def wave_end(obs, marks: tuple, error: str | None = None,
             **tags) -> None:
    g_devwatch.wave_end(obs, marks, error=error, **tags)


def note_cost(kernel: str, bucket, thunk, modeled_bytes=None) -> None:
    g_devwatch.note_cost(kernel, bucket, thunk,
                         modeled_bytes=modeled_bytes)


def maybe_enable() -> None:
    """Enable iff OSSE_DEVWATCH=1 — import-time wiring in the device
    layers and the server; a true no-op otherwise."""
    if os.environ.get("OSSE_DEVWATCH", "") == "1":
        enable()
