"""From the profiler's trace to numbers. Kept with the benchmark so that every
PR computes the same numbers the same way.

``reduce(path, span_s)`` reads one ``.xplane.pb`` with nothing but JAX and
returns, for the traced span (the first ``span_s`` seconds after the first
event, or all of it): the device's busy seconds (the union of the intervals in
which an operation ran, averaged over the chips), each program's and each
operation's device seconds, and the longest idle gaps with what the host was
doing in them.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def short_name(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list]:
    """(union length in s, the gaps between merged intervals as (start, end))."""
    busy, gaps = 0, []
    end = None
    for a, b in sorted(intervals):
        if end is None:
            start, end = a, b
        elif a <= end:
            end = max(end, b)
        else:
            busy += end - start
            gaps.append((end, a))
            start, end = a, b
    if end is not None:
        busy += end - start
    return busy / 1e9, gaps


def reduce(path: str, span_s: float | None = None, planes=None) -> dict:
    if planes is None:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        planes = list(pd.planes)
    dev = [p for p in planes if p.name.startswith("/device:TPU:")
           and "SparseCore" not in p.name]
    host = [p for p in planes if p.name.startswith("/host:")]
    out = {"planes": [p.name for p in planes], "busy_s": None,
           "window_s": None, "ops": [], "modules": {}, "gaps": [],
           "lines": {}}
    if not dev:
        return out
    t_first = min((e.start_ns for p in dev for ln in p.lines
                   for e in ln.events), default=None)
    if t_first is None:
        return out
    t_cut = t_first + int(span_s * 1e9) if span_s else None
    busy_per_chip, op_s, mod_s = [], defaultdict(float), defaultdict(float)
    gaps_all = []
    t_last = t_first
    for p in dev:
        out["lines"][p.name] = [ln.name for ln in p.lines]
        op_iv = []
        names_ops = [ln for ln in p.lines if ln.name in OPS_LINES] or \
            [ln for ln in p.lines if ln.name not in MODULE_LINES
             and ln.name != "Steps"]
        for ln in names_ops:
            for e in ln.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if t_cut is not None:
                    if a >= t_cut:
                        continue
                    b = min(b, t_cut)
                op_iv.append((a, b))
                op_s[short_name(e.name)] += (b - a) / 1e9
                t_last = max(t_last, b)
        for ln in p.lines:
            if ln.name in MODULE_LINES:
                for e in ln.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if t_cut is not None:
                        if a >= t_cut:
                            continue
                        b = min(b, t_cut)
                    mod_s[e.name] += (b - a) / 1e9
        busy, gaps = union_seconds(op_iv)
        busy_per_chip.append(busy)
        gaps_all += gaps
    out["busy_s"] = sum(busy_per_chip) / len(busy_per_chip)
    out["window_s"] = (span_s if span_s else (t_last - t_first) / 1e9)
    out["ops"] = sorted(([k, v] for k, v in op_s.items()),
                        key=lambda kv: -kv[1])
    out["modules"] = dict(mod_s)
    out["gaps"] = attribute_gaps(gaps_all, host)
    return out


def attribute_gaps(gaps: list[tuple[int, int]], host_planes, top: int = 300
                   ) -> list[list]:
    """The longest idle gaps, each named by the host event that overlaps it
    most, summed by name: [[name, seconds], ...], longest first."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    if not gaps:
        return []
    ev = []
    for p in host_planes:
        for ln in p.lines:
            for e in ln.events:
                if e.duration_ns > 0:
                    ev.append((e.start_ns, e.start_ns + e.duration_ns,
                               e.name))
    ev.sort()
    starts = [e[0] for e in ev]
    longest = max((b - a for a, b, _ in ev), default=0)
    by = defaultdict(float)
    for a, b in gaps:
        # the innermost event that covers at least half of the gap names it
        # (a thread's outermost span covers every gap and says nothing);
        # failing that, the event that overlaps it most
        best, best_ov, best_len = "host: nothing traced", 0, None
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_right(starts, b)
        for ea, eb, name in ev[lo:hi]:
            ov = min(b, eb) - max(a, ea)
            if ov <= 0:
                continue
            covers = 2 * ov >= b - a
            if covers and (best_len is None or eb - ea < best_len):
                best, best_ov, best_len = name, ov, eb - ea
            elif best_len is None and ov > best_ov:
                best, best_ov = name, ov
        by[best] += (b - a) / 1e9
    return sorted(([k, v] for k, v in by.items()), key=lambda kv: -kv[1])
