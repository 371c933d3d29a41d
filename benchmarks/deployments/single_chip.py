"""Deployment ``single_chip``: one shard of a box, served in this process.

``SearchHTTPServer`` (HTTP handler -> admission gate -> QueryBatcher ->
resident loop -> DeviceIndex) over one collection on one chip. This file is
everything the benchmark knows of the program: how to index the corpus, start
the server, drive the direct entry (``engine.search_device_batch``, which has
no wait bound, so set-up can compile through it), see which wave programs a
query rides, and read the program's spans and counters (``g_stats``,
jitwatch).

The program keeps no record of which program a query dispatched, so during
set-up, and only then, ``DeviceIndex._costed`` (the one door every wave
program goes through, with its name and shape bucket) is wrapped to note them.
``seal()`` puts the program's own method back before the load generator is
told to go: the window runs the program as shipped.
"""

from __future__ import annotations

import inspect
import json
import logging
import os
import re
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

from lib import spec

CHILD = spec.BENCH / "lib" / "corpus_child.py"
KEPT_CORPORA = 8        # corpora kept in _work/ (the check reuses 6 seeds)


def _key_str(name: str, bucket) -> str:
    return name.rsplit(".", 1)[-1] + ":" + ",".join(str(int(x)) for x in bucket)


def key_class(key: str) -> str | None:
    """The class a program key belongs to for the cover: programs of one
    class differ by the batch bucket B, which a burst can reach."""
    name, _, nums = key.partition(":")
    b = [int(x) for x in nums.split(",")]
    if name == "_direct_cube":        # (B, T, Rp, Lp, k2, n_sel): tail class
        return f"fd:Lp{b[3]}"
    if name == "_two_phase":          # (B, Rd, Rs, Lsp, kappa, k2): rung
        return f"f1:k{b[4]}:{b[5]}"
    return None


def key_cost_class(key: str) -> str:
    """``slow`` for programs that compile for a minute or more."""
    return "slow" if key.startswith("_direct_cube") else "fast"


BURST = 5       # queries of one class in one batch reach the next B bucket

_CACHE_RE = re.compile(r"(?i)cache (hit|miss) for '([^']+)' with key '[^']*?-(\w{12})")


class _CacheLog(logging.Handler):
    """JAX's own word on every program it asked the persistent cache for:
    (program, hit or miss, the key's first letters)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.seen: list[list[str]] = []

    def emit(self, record: logging.LogRecord) -> None:
        m = _CACHE_RE.search(record.getMessage())
        if m:
            self.seen.append([m.group(2), m.group(1).lower(), m.group(3)])


class Deployment:
    BURST = BURST
    key_class = staticmethod(key_class)
    key_cost_class = staticmethod(key_cost_class)

    def __init__(self, run, cfg: dict, rehearse: bool = False):
        self.run, self.cfg, self.rehearse = run, cfg, rehearse
        self.dep = cfg["deployment"]
        self.corpus = cfg["corpus"]
        self.keys: dict[str, dict] = {}       # key -> {first_s, n}
        self._recent: list[str] = []
        self._dry = threading.local()
        self.srv = self.coll = self.di = None
        self.corpus_dir: Path | None = None
        self._children: list = []
        self._parts: list[Path] = []
        self.corpus_kept = False
        self._costed = None         # the program's own, while ours is in

    # -------------------------------------------------------------- corpus
    def start_corpus(self, seed: int, docs: int) -> None:
        """Kept corpus, or children that index it while the chip is reached."""
        root = spec.WORK / "corpus"
        self.corpus_dir = root / f"{self.cfg['name']}-s{seed}-d{docs}"
        self.seed, self.docs = seed, docs
        if (self.corpus_dir / "READY").is_file():
            self.corpus_kept = True
            os.utime(self.corpus_dir / "READY")
            return
        shutil.rmtree(self.corpus_dir, ignore_errors=True)
        if root.is_dir():       # keep the newest few: the disk is counted
            old = sorted((p for p in root.iterdir()
                          if (p / "READY").is_file()),
                         key=lambda p: (p / "READY").stat().st_mtime)
            for p in old[:max(len(old) - (KEPT_CORPORA - 1), 0)]:
                shutil.rmtree(p, ignore_errors=True)
        self.corpus_dir.mkdir(parents=True)
        n = max(1, min(self.dep.get("corpus_children", 12),
                       (os.cpu_count() or 2) - 1, docs // 200 or 1))
        step = -(-docs // n)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        for k in range(n):
            lo, hi = k * step, min((k + 1) * step, docs)
            if lo >= hi:
                break
            part = self.corpus_dir / f"part-{k}"
            self._parts.append(part)
            self._children.append(self.run.spawn(
                [sys.executable, str(CHILD),
                 "--generator", self.corpus["generator"],
                 "--params", json.dumps(self.corpus["params"]),
                 "--seed", str(seed), "--lo", str(lo), "--hi", str(hi),
                 "--out", str(part)],
                env=env, stdout=sys.stderr, stderr=sys.stderr))

    def finish_corpus(self, limit: float) -> None:
        """Wait for the children (with a limit) and merge their slices into
        the served collection through the Rdbs' own ``add``."""
        from open_source_search_engine_tpu.index.collection import Collection
        serve = self.corpus_dir / "serve"
        if self.corpus_kept:
            return
        t_end = time.perf_counter() + limit
        for p in self._children:
            rc = self.run.wait_proc(
                p, max(t_end - time.perf_counter(), 1.0), "corpus child")
            if rc != 0:
                raise RuntimeError(f"corpus child {p.args[-1]} exited {rc}")
        from open_source_search_engine_tpu.utils.membudget import g_membudget
        g_membudget.set_limit(int(self.dep["max_mem_bytes"]))
        coll = Collection("main", serve)
        lens, ids = [], []
        for part in self._parts:
            sub = Collection("main", part)
            for name in ("posdb", "clusterdb"):
                getattr(coll, name).add(getattr(sub, name).get_all().keys)
            tb = sub.titledb.get_all()
            coll.titledb.add(tb.keys, tb.payloads())
            coll.doc_added(sub.num_docs)
            w = np.load(part / "words.npz")
            lens.append(w["lens"])
            ids.append(w["ids"])
            sub.close()
        coll.dump_all()
        coll.save()
        coll.close()
        np.savez(self.corpus_dir / "words.npz", lens=np.concatenate(lens),
                 ids=np.concatenate(ids))
        for part in self._parts:
            shutil.rmtree(part, ignore_errors=True)
        (self.corpus_dir / "READY").write_text(f"{self.docs}\n")

    def words(self) -> tuple[np.ndarray, np.ndarray]:
        """The pages' word ids, as the benchmark's own generator made them
        (nothing of the program's): what the reference is given."""
        w = np.load(self.corpus_dir / "words.npz")
        return w["lens"], w["ids"]

    # ---------------------------------------------------------------- chip
    def reach_chip(self) -> dict:
        """Import jax, settle the compile cache, say what device this is."""
        if self.rehearse:
            from open_source_search_engine_tpu.parallel.routecheck import \
                ROUTE_ENV
            os.environ.update(ROUTE_ENV)
            os.environ.setdefault("OSSE_PALLAS", "force")
        # the one compile cache: inside the checkout, at a fixed path, unless
        # the machine names one (then the program's rule takes that)
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              str(spec.WORK / "xla_cache"))
        import jax

        from open_source_search_engine_tpu.utils import compilecache
        self.cache_dir = compilecache.configure()
        d0 = jax.devices()[0]
        self.jax = jax
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": len(jax.devices())}

    def start_trace(self, trace_dir: str) -> None:
        """The profiler, without the Python tracer: it slows the host that
        the measured path runs on, and the device's lines do not need it."""
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        self.jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.devices()]
        return int(max(peaks))

    # --------------------------------------------------------------- serve
    def start(self) -> dict:
        from open_source_search_engine_tpu.query import devindex, engine
        from open_source_search_engine_tpu.serve.server import \
            SearchHTTPServer
        from open_source_search_engine_tpu.utils import jitwatch
        from open_source_search_engine_tpu.utils.parms import Conf
        jitwatch.enable()
        self._cache_log = _CacheLog()
        lg = logging.getLogger("jax._src.compiler")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(self._cache_log)
        lg.propagate = False
        self.engine = engine
        costed = devindex.DeviceIndex._costed
        if list(inspect.signature(costed).parameters)[:5] != [
                "self", "name", "bucket", "modeled_bytes", "fn"]:
            raise RuntimeError(
                "DeviceIndex._costed no longer takes (name, bucket, "
                "modeled_bytes, fn, ...): this deployment file reads the "
                "program keys of set-up there, and cannot be trusted to "
                f"name them now (it takes {inspect.signature(costed)}); a "
                "benchmark PR has to bring a deployment file that can")
        dep = self

        def spy(di, name, bucket, modeled, fn, *args, **statics):
            key = _key_str(name, bucket)
            dep._recent.append(key)
            if getattr(dep._dry, "on", False):
                return None
            t0 = time.perf_counter()
            out = costed(di, name, bucket, modeled, fn, *args, **statics)
            rec = dep.keys.setdefault(
                key, {"first_s": time.perf_counter() - t0, "n": 0})
            rec["n"] += 1
            return out

        self._costed = costed
        devindex.DeviceIndex._costed = spy
        self.srv = SearchHTTPServer(
            self.corpus_dir / "serve", port=0,
            conf=Conf(max_mem=int(self.dep["max_mem_bytes"])))
        self.coll = self.srv.colldb.get(self.dep["collection"])
        self.coll.conf.pqr_enabled = bool(self.dep["pqr_enabled"])
        if self.coll.num_docs != self.docs:
            raise RuntimeError(f"corpus holds {self.coll.num_docs} pages, "
                               f"not {self.docs}")
        self.srv.start()
        t0 = time.perf_counter()
        self.di = engine.get_device_index(self.coll)
        self.jax.block_until_ready(self.di.d_cube)
        return {"base_s": round(time.perf_counter() - t0, 2),
                "D_cap": int(self.di.D_cap),
                "shapes": {k: list(getattr(self.di, k).shape) for k in
                           ("d_cube", "d_payload", "d_doc", "d_dense_imp")},
                "resident_bytes": int(self.di.resident_bytes()),
                "port": self.srv.port}

    @property
    def port(self) -> int:
        return self.srv.port

    def direct(self, queries: list[str]) -> list[str]:
        """One batch through the direct entry; the program keys it rode."""
        self._recent = []
        self.engine.search_device_batch(
            self.coll, list(queries), topk=int(self.dep["page"]),
            with_snippets=False)
        return list(self._recent)

    def dry_keys(self, queries: list[str]) -> list[list[str]]:
        """Host only: plan and route each query alone and note the program it
        would dispatch, without dispatching (first rung only). It counts on
        ``issue_batch`` handing on what ``_costed`` returns unread; where that
        ends, this raises in set-up and the run ends without a result."""
        from open_source_search_engine_tpu.query.compiler import compile_query
        out = []
        self._dry.on = True
        try:
            for q in queries:
                self._recent = []
                self.di.issue_batch([compile_query(q, 0)], topk=64, lang=0)
                out.append(list(self._recent))
        finally:
            self._dry.on = False
        return out

    def compiled_since(self) -> dict:
        """Programs compiled or loaded since the last call (jitwatch's
        record, then reset): what a stage cost in programs, by name."""
        from open_source_search_engine_tpu.utils import jitwatch
        snap = jitwatch.snapshot()
        jitwatch.reset()
        seen, self._cache_log.seen = self._cache_log.seen, []
        return {"compile_s": round(snap["totals"]["compile_s"], 1),
                "programs": snap["totals"]["compiles"],
                "cache": [c for c in seen if c[1] == "miss"
                          or self.wave_program(c[0])]}

    def seal(self) -> None:
        """The last thing before the window. After the walk every query's
        plan, compiled form and rendered page is cached: flushed, so that the
        window does the work its users' first sight of a query would. And the
        program's own ``_costed`` goes back in place of set-up's wrapper."""
        from open_source_search_engine_tpu.cache import g_cacheplane
        from open_source_search_engine_tpu.query import devindex
        g_cacheplane.flush()
        self.coll.titlerec_cache.clear()
        devindex.DeviceIndex._costed = self._costed

    def counters(self) -> dict:
        """The program's counters and span totals, flat."""
        from open_source_search_engine_tpu.utils.stats import g_stats
        snap = g_stats.snapshot()
        out = {k: float(v) for k, v in snap["counters"].items()}
        for name, lat in snap["latencies"].items():
            out[f"{name}.count"] = float(lat["count"])
            out[f"{name}.total_ms"] = float(lat["avg_ms"] * lat["count"])
        out["server.result_cache_hits"] = float(
            self.srv.stats.get("result_cache_hits", 0))
        out["server.queries"] = float(self.srv.stats.get("queries", 0))
        return out

    def wave_program(self, op_or_module_name: str) -> bool:
        """Is this device-trace module one of the wave programs?"""
        return any(s in op_or_module_name for s in
                   ("_direct_cube", "_two_phase", "_full_cube"))

    def stop(self) -> None:
        from open_source_search_engine_tpu.query import devindex
        if self.srv is not None:
            self.srv.stop()
        if self._costed is not None:        # a run cut in set-up
            devindex.DeviceIndex._costed = self._costed
