"""chip_smoke.py — the served query path, once, on the chip.

The quickest proof that this program still starts on a TPU: one
process builds the toy corpus through the real indexing pipeline,
serves it from ``SearchHTTPServer`` (HTTP handler → admission →
QueryBatcher → resident loop → DeviceIndex), drives every kernel route
with a few dozen requests, and compares every answer with the host flat
path (``engine.search``). Anything that would hide the device — a host
fallback, a host index build, a NumPy stand-in for a native library, an
XLA program where the Pallas kernel should be — fails the run.

    python chip_smoke.py              # one chip, 100,000 documents
    python chip_smoke.py --docs 300   # CPU rehearsal: every phase runs,
                                      # then the device check fails
                                      # (without --docs a CPU fails at
                                      # once)
    python chip_smoke.py --chips 4    # the mesh-resident path only:
                                      # 4 shards, 40,000 documents

Every line on stdout is one JSON object; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and the exit code is 0 only with it. Nothing is caught and carried past:
a phase that raises ends the run with its traceback.

A cold chip compiles. A program the path has not run yet costs seconds
(the XLA waves) to ~105 s (each fused FD variant), and the server's
waits are sized for a warm system (60 s in the batcher, 120 s on a
resident ticket), past which a request degrades to the host path —
counted since this script exists (``serve.device_fallback``). So each
group of queries first goes through ``engine.search_device`` (the
``search --device`` entry point, no wait bound) and pays the compiles;
the same queries then cross HTTP, where a compile, a retrace or a
fallback is a failure.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
import urllib.parse
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax

from benchmarks.corpora import zipf_html
from benchmarks.lib import spec
from open_source_search_engine_tpu import native
from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.parallel import sharded_search
from open_source_search_engine_tpu.parallel.routecheck import ROUTE_ENV
from open_source_search_engine_tpu.parallel.sharded import \
    ShardedCollection
from open_source_search_engine_tpu.query import devindex, engine
from open_source_search_engine_tpu.serve.server import SearchHTTPServer
from open_source_search_engine_tpu.utils import (compilecache, devwatch,
                                                 jitwatch)
from open_source_search_engine_tpu.utils.parms import Conf
from open_source_search_engine_tpu.utils.stats import g_stats

#: the toy corpus is the benchmark's: one rule (``zipf_html``), the
#: parameters of its ``gbshard-80k`` configuration, a seed of the smoke's
CORPUS_SEED = 42
CORPUS_PARAMS = spec.load_json(
    spec.BENCH / "configs" / "gbshard-80k.json")["corpus"]["params"]


def corpus(n_docs: int):
    """(url, html) of the toy corpus's first ``n_docs`` pages."""
    return zipf_html.pages(CORPUS_SEED, 0, n_docs, CORPUS_PARAMS)


#: documents of the bulk inject that pushes a 4-word query's un-dumped
#: postings past FD_SCATTER_MAX_LANES (4 words × 16 stored positions ×
#: 640 docs = 40,960 > 32,768): the everyday way onto the generic F2
#: kernel is a heavy query over a large live delta
BULK_DOCS = 640
BULK_WORDS = ("word1", "word2", "word3", "word4")

#: how deep the host flat path is fetched for the comparison
HOST_DEPTH = 50

#: gb.conf ``maxmem`` for the smoke's instance (the chip's host has
#: 40 GiB)
MAX_MEM = 32 << 30

FAILED: list[str] = []


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def check(ok: bool, what: str) -> bool:
    if not ok:
        FAILED.append(what)
    return ok


def device_record() -> dict:
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices())}


def http(port: int, path: str, data: bytes | None = None) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                data=data, timeout=300) as r:
        return json.loads(r.read())


def search_url(q: str) -> str:
    return "/search?format=json&n=10&q=" + urllib.parse.quote(q)


def index_flat(coll, n_docs: int) -> None:
    """The toy corpus through the real pipeline, 512 pages a batch."""
    chunk: list = []
    for doc in corpus(n_docs):
        chunk.append(doc)
        if len(chunk) >= 512:
            docproc.index_batch(coll, chunk)
            chunk = []
    if chunk:
        docproc.index_batch(coll, chunk)


def dump(coll) -> None:
    """Memtable → runs: the served queries read the on-disk base."""
    # PQR's per-domain demotion is rank-dependent, so it stamps
    # different scores onto docs that tie in base score — compare the
    # undemoted ranking (the benchmark's configurations do the same)
    coll.conf.pqr_enabled = False
    coll.posdb.dump()
    coll.titledb.dump()
    coll.save()


def finish(device: dict) -> int:
    if FAILED:
        emit(ok=False, device=device, failed=FAILED)
        return 1
    emit(ok=True, device=device)
    return 0


# --------------------------------------------------------------- one chip

def _route_groups(tag: str) -> list[dict]:
    """The requests, grouped by the wave program they ride. Within a
    group every query has the same shape bucket (checked on the
    100,000-document corpus: heavy words have dense + cube rows, so
    these FD queries are pure quarter-row waves), so on a cold chip a
    group costs one kernel compile."""
    a, b, c = (f"zz{tag}a", f"zz{tag}b", f"zz{tag}c")
    rot = [" ".join(BULK_WORDS[i:] + BULK_WORDS[:i]) for i in range(4)]
    return [
        {"route": "single",
         # word3's speculative κ=256 rung misses (its top scores tie
         # past the f16 bound's rounding), so the escalation ladder
         # runs once: a second two-phase program
         "queries": ["word1", "word2", "word3", "word5", "word6",
                     "word7", "word10"]},
        {"route": "fd",
         "queries": ["word1 word2", "word2 word3", "word1 word3",
                     "word5 word6", "word6 word7", "word7 word8",
                     "word2 word7", "word1 word2 word3",
                     "word3 word4 word5", "word5 word6 word7"]},
        # the injected document's own words: drivers of df 1, so the
        # two-phase kernel prunes (F1, several scored groups)
        {"route": "f1",
         "inject": [(f"http://smoke.test/{tag}",
                     f"<html><head><title>{a} {b}</title></head><body>"
                     f"<p>{a} {b} {c} word9 {a} word9 {b}.</p></body>"
                     "</html>")],
         "must_see": (a, f"http://smoke.test/{tag}"),
         "queries": [f"{a} {b}", f"{b} {c}", f"{c} word9",
                     f"{a} word9", a]},
        {"route": "f2",
         "inject": [(f"http://bulk{i % 89}.smoke.test/{tag}/{i}",
                     "<html><head><title>bulk</title></head><body><p>"
                     + " ".join(BULK_WORDS * 16) + f" bulk{i}.</p>"
                     "</body></html>") for i in range(BULK_DOCS)],
         "queries": rot},
    ]


def _tie(a: float, b: float) -> bool:
    return abs(a - b) <= 1e-5 * max(abs(a), abs(b))


def _compare(q: str, ans: dict, search) -> float:
    """One served answer against a reference path (``search(**kw)``):
    recall@10 — the relevant set is every reference docid scoring ≥
    the reference's 10th-best score — with
    the tie-run semantics of ``routecheck.assert_tie_run_parity``: this
    corpus saturates, so a heavy query's top scores tie across
    thousands of documents and the two paths pick different members of
    the run the cut falls in. The score ladder must agree rank by rank
    with the reference's own (site-clustered) page; a served document
    is relevant when the reference's UNCLUSTERED list gives it the same
    score, at or above the floor — or, where that list (HOST_DEPTH
    deep) ends inside the floor's tie run, when it ties that run."""
    host = search(topk=10)
    deep = search(topk=HOST_DEPTH, site_cluster=False).results
    served, top = ans["results"][:10], host.results[:10]
    check(ans["totalMatches"] == host.total_matches,
          f"{q!r}: {ans['totalMatches']} matches, reference "
          f"{host.total_matches}")
    check(len(served) == len(top)
          and all(_tie(r["score"], h.score)
                  for r, h in zip(served, top)),
          f"{q!r}: score ladder {[r['score'] for r in served]} vs "
          f"reference {[h.score for h in top]}")
    if not top:
        return 1.0 if not served else 0.0
    floor = top[-1].score
    scores = {h.docid: h.score for h in deep}
    cut = deep[-1].score if len(deep) >= HOST_DEPTH else None
    got = 0
    for r in served:
        hs = scores.get(r["docId"])
        if hs is not None:
            ok = _tie(r["score"], hs) and (hs >= floor
                                           or _tie(hs, floor))
        else:
            ok = (cut is not None and _tie(r["score"], cut)
                  and _tie(cut, floor))
        got += ok
    return got / len(top)


def run_one_chip(n_docs: int, device: dict) -> None:
    if n_docs < 20000:
        # rehearsal sizes: scale the dense/cube thresholds so the tiny
        # corpus still has dense rows, cube rows and every route; on a
        # CPU the fused kernels run in interpret mode, so the code
        # around them is the code the chip runs
        os.environ.update(ROUTE_ENV)
        if device["platform"] == "cpu":
            os.environ.setdefault("OSSE_PALLAS", "force")
    if device["platform"] != "cpu":
        emit(phase="peaks", device_kind=device["kind"],
             row=list(devwatch.peaks_row(device["kind"])))

    # --- native libraries: built from source as git commits it ---------
    ok = native.available() and native.get_doccore() is not None
    emit(phase="native", native="loaded" if ok else "fallback")
    check(ok, "native: fallback")

    jitwatch.enable()
    # observe every wave dispatch: (kernel, bucket) → the jitted
    # function, its argument shapes and statics — what routes ran, and
    # what to lower at the end to see the kernel inside the program
    dispatched: dict[tuple, tuple] = {}
    costed = devindex.DeviceIndex._costed

    def spy(self, name, bucket, modeled, fn, *args, **statics):
        key = (name, tuple(int(x) for x in bucket))
        if key not in dispatched:
            dispatched[key] = (fn, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args),
                statics)
        return costed(self, name, bucket, modeled, fn, *args, **statics)

    devindex.DeviceIndex._costed = spy

    base = tempfile.mkdtemp(prefix="osse_smoke_")
    # max_mem is the deployment's setting, sized to its machine: the
    # membudget charges the resident set (5.8 GB at 100,000 documents)
    # to the process budget, and under the 4 GiB default every
    # host-side reserve parks the tenant (a base rebuild per park)
    srv = SearchHTTPServer(base, port=0, conf=Conf(max_mem=MAX_MEM))
    try:
        _serve_and_check(n_docs, srv, dispatched)
    finally:
        srv.stop()
        devindex.DeviceIndex._costed = costed
        shutil.rmtree(base, ignore_errors=True)


def _serve_and_check(n_docs: int, srv, dispatched: dict) -> None:
    coll = srv.colldb.get("main")
    t0 = time.perf_counter()
    index_flat(coll, n_docs)
    dump(coll)
    build_s = time.perf_counter() - t0
    emit(phase="corpus", docs=coll.num_docs, build_s=round(build_s, 1),
         docs_per_s=round(n_docs / build_s, 1))
    check(coll.num_docs == n_docs, f"corpus: {coll.num_docs} docs")

    srv.start()

    # --- device base: posting sort/dedup/pack + dense/cube rows --------
    t0 = time.perf_counter()
    di = engine.get_device_index(coll)
    jax.block_until_ready(di.d_cube)
    base_s = time.perf_counter() - t0
    tot = jitwatch.snapshot()["totals"]
    emit(phase="device_base", seconds=round(base_s, 1), D_cap=di.D_cap,
         resident_bytes=di.resident_bytes(),
         compiles=tot["compiles"],
         compile_s=round(tot["compile_s"], 1))
    jitwatch.reset()

    steady_compiles = steady_retraces = 0
    recalls: list[float] = []
    for g in _route_groups("smoke"):
        qs = g["queries"]
        for url, html in g.get("inject", ()):
            r = http(srv.port, "/inject?url="
                     + urllib.parse.quote(url, safe=""), html.encode())
            check("docId" in r, f"inject {url}: {r}")
        # warm-up: the refresh after an inject, and every program the
        # group's queries ride, compile here (the first answer pays
        # most of it; a query that escalates adds its rung)
        t0 = time.perf_counter()
        engine.search_device(coll, qs[0], topk=10)
        first_s = time.perf_counter() - t0
        for q in qs[1:]:
            engine.search_device(coll, q, topk=10)
        warm_s = time.perf_counter() - t0
        warm = jitwatch.snapshot()["totals"]
        jitwatch.reset()
        # the served pass: every query of the group over HTTP
        answers, ms = [], []
        for q in qs:
            t0 = time.perf_counter()
            answers.append(http(srv.port, search_url(q)))
            ms.append(1000 * (time.perf_counter() - t0))
        steady = jitwatch.snapshot()["totals"]
        steady_compiles += steady["compiles"]
        steady_retraces += steady["retraces"]
        if "must_see" in g:
            q, url = g["must_see"]
            got = [r["url"] for r in answers[qs.index(q)]["results"]]
            check(got[:1] == [url], f"inject not visible: {q} → {got}")
        # the reference: the host flat path on the same index state
        g_recalls = []
        for q, ans in zip(qs, answers):
            rec = _compare(q, ans, functools.partial(
                engine.search, coll, q, with_snippets=False))
            check(rec == 1.0, f"{q!r}: recall@10 {rec}")
            g_recalls.append(rec)
        recalls += g_recalls
        ref = jitwatch.snapshot()["totals"]
        jitwatch.reset()  # the reference's own compiles are not the path's
        emit(phase="route", route=g["route"], queries=len(qs),
             first_answer_s=round(first_s, 2),
             warm_up_s=round(warm_s, 2),
             warm_up_compiles=warm["compiles"],
             warm_up_compile_s=round(warm["compile_s"], 1),
             reference_compiles=ref["compiles"],
             reference_compile_s=round(ref["compile_s"], 1),
             served_ms_median=statistics.median(ms),
             served_ms_max=max(ms),
             served_compiles=steady["compiles"],
             served_retraces=steady["retraces"],
             recall_at_10=min(g_recalls))

    # --- did every route run, on the device, with its kernel? ----------
    lat = g_stats.snapshot()["latencies"]
    waves = {k: v["count"] for k, v in lat.items()
             if k.startswith("devindex.wave_")}
    # one index object served the whole run: a parked tenant or a
    # moved run set would have rebuilt the base behind the requests
    check(engine.get_device_index(coll) is di,
          "device index was rebuilt during the run")
    rc = dict(di.route_counts)
    programs = sorted([k, list(b)] for k, b in dispatched)
    emit(phase="routes", route_counts=rc, wave_timers=waves,
         programs=programs, escalations=di.escalations)
    for kind in ("f1", "fd", "f2"):
        check(rc[kind] > 0, f"route {kind} never ran")
    check(waves.get("devindex.wave_f1_n1", 0) > 0
          and waves.get("devindex.wave_f2_n1", 0) > 0,
          f"wave timers: {waves}")
    # every two-phase wave (single-term and F1 groups alike: one width
    # a rung since PR 30) rode a program of the set the index
    # enumerated, which start-up had dispatched before the first query
    # (a rung above the ladder, which a query may escalate to, is not
    # of the set)
    two_phase = {b for k, b in dispatched if k == "devindex._two_phase"
                 and b[4] <= devindex.F1_RUNGS[-1] * devindex.KAPPA_FLOOR}
    check(bool(two_phase) and two_phase <= set(di.f1_programs()),
          f"two-phase waves outside the enumerated set: "
          f"{sorted(two_phase - set(di.f1_programs()))}")
    kernels = {}
    for (name, bucket), (fn, sds, statics) in dispatched.items():
        if name == "devindex._two_phase":
            continue  # phase 2 is κ-wide: below the fused kernels' MIN_D
        text = fn.lower(*sds, **statics).as_text()
        kernels[f"{name}{list(bucket)}"] = text.count("tpu_custom_call")
    emit(phase="kernels", tpu_custom_calls=kernels)
    for name in ("devindex._direct_cube", "devindex._full_cube"):
        hit = [v for k, v in kernels.items() if k.startswith(name)]
        check(bool(hit) and all(v > 0 for v in hit),
              f"kernels: no Pallas kernel in {name}")

    counters = g_stats.snapshot()["counters"]
    zero = {k: counters.get(k, 0) for k in
            ("serve.device_fallback", "build.devbuild_fallback",
             "native.fallback")}
    emit(phase="fallbacks", **zero, served_compiles=steady_compiles,
         served_retraces=steady_retraces,
         device_base=counters.get("build.device_base", 0),
         device_delta=counters.get("build.device_delta", 0),
         recall_at_10=min(recalls), answers=len(recalls))
    for k, v in zero.items():
        check(v == 0, f"{k} = {v}")
    check(counters.get("build.device_base", 0) > 0,
          "device base never built on the device")
    check(steady_compiles == 0 and steady_retraces == 0,
          f"served pass compiled {steady_compiles} / retraced "
          f"{steady_retraces}")


# ------------------------------------------------------------- four chips

#: the mesh phase's requests: corpus-wide drivers (candidate axes past
#: the fused kernel's MIN_D on every shard) and rare ones
MESH_QUERIES = ["word1", "word2", "word1 word2", "word2 word3",
                "word5 word6", "word1 word2 word3",
                "word1500", "word1999", "word700 word900"]


def run_mesh(n_docs: int) -> None:
    """--chips 4: a 4-shard ShardedCollection served through the
    mesh-resident path (``serve_mesh``: one ``shard_map`` program per
    wave, Msg3a merge + site dedup in-jit), each answer compared with
    the host merge (``sharded_search``) and with the flat path over the
    union of the shards' documents."""
    jitwatch.enable()
    base = tempfile.mkdtemp(prefix="osse_smoke_mesh_")
    srv = None
    try:
        # --- the corpus twice: sharded by docid, and flat (the union) --
        t0 = time.perf_counter()
        sc = ShardedCollection("main", os.path.join(base, "mesh"),
                               n_shards=4)
        for doc in corpus(n_docs):
            sc.index_document(*doc)
        flat = Collection("main", os.path.join(base, "flat"))
        index_flat(flat, n_docs)
        for c in sc.shards + [flat]:
            dump(c)
        emit(phase="corpus", docs=sc.num_docs,
             shard_docs=[c.num_docs for c in sc.shards],
             build_s=round(time.perf_counter() - t0, 1))
        check(sc.num_docs == n_docs == flat.num_docs,
              f"corpus: {sc.num_docs} sharded, {flat.num_docs} flat")

        srv = SearchHTTPServer(
            base, port=0, sharded=sc,
            conf=Conf(serve_mesh=True, max_mem=MAX_MEM))
        srv.start()

        # --- warm-up: the mesh programs compile here (no wait bound), and
        # four distinct devices must hold the four shards' blocks -------
        mr = engine.get_mesh_resident(sc)
        msi = mr._serve_index()
        mesh_ids = [d.id for d in msi.mesh.devices.flat]
        jitwatch.reset()
        t0 = time.perf_counter()
        placed = None
        for q in MESH_QUERIES:
            pending = msi.issue_batch([q],
                                      topk=max(10, engine.PQR_SCAN))
            if placed is None:
                # shard s's [1, B, ...] block of a staged operand, and
                # the device it sits on
                placed = sorted(
                    (sh.index[0].start, sh.device.id) for sh in
                    pending.waves[0].args["doc_idx"].addressable_shards)
            msi.collect_batch(pending)
        warm = jitwatch.snapshot()["totals"]
        warm_s = time.perf_counter() - t0
        jitwatch.reset()
        emit(phase="mesh", mesh_devices=mesh_ids,
             shard_block_devices=placed)
        check(len(set(mesh_ids)) == 4
              and placed == list(zip(range(4), mesh_ids)),
              f"shards not on four distinct devices: mesh {mesh_ids}, "
              f"blocks {placed}")

        # --- served: HTTP → serve_mesh → MeshResident.serve ------------
        answers, ms = [], []
        for q in MESH_QUERIES:
            t0 = time.perf_counter()
            answers.append(http(srv.port, search_url(q)))
            ms.append(1000 * (time.perf_counter() - t0))
        steady = jitwatch.snapshot()["totals"]
        loop = mr.serve_loop()
        check(loop.alive and loop.waves_issued >= len(MESH_QUERIES),
              f"mesh loop issued {loop.waves_issued} waves")

        # --- references: host merge, and the flat path over the union --
        recalls = {"host_merge": [], "flat": []}
        for q, ans in zip(MESH_QUERIES, answers):
            for name, ref in (
                    ("host_merge", functools.partial(
                        sharded_search, sc, q, with_snippets=False)),
                    ("flat", functools.partial(
                        engine.search, flat, q, with_snippets=False))):
                rec = _compare(f"{name} {q}", ans, ref)
                check(rec == 1.0, f"{q!r}: recall@10 {rec} vs {name}")
                recalls[name].append(rec)
        counters = g_stats.snapshot()["counters"]
        emit(phase="mesh_served", queries=len(MESH_QUERIES),
             warm_up_s=round(warm_s, 1),
             warm_up_compiles=warm["compiles"],
             warm_up_compile_s=round(warm["compile_s"], 1),
             served_ms_median=statistics.median(ms),
             served_ms_max=max(ms),
             served_compiles=steady["compiles"],
             served_retraces=steady["retraces"],
             waves_issued=loop.waves_issued,
             recall_vs_host_merge=min(recalls["host_merge"]),
             recall_vs_flat=min(recalls["flat"]),
             devbuild_fallback=counters.get(
                 "build.devbuild_fallback", 0))
        check(steady["compiles"] == 0 and steady["retraces"] == 0,
              f"served pass compiled {steady['compiles']} / retraced "
              f"{steady['retraces']}")
        check(counters.get("build.devbuild_fallback", 0) == 0,
              "build.devbuild_fallback > 0")
    finally:
        if srv is not None:
            srv.stop()
        shutil.rmtree(base, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--docs", type=int,
                    help="corpus size, to rehearse on a CPU (default "
                         "100,000; with --chips 4, 40,000: 10,000 to "
                         "a shard)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh-resident path and its references, "
                         "and no other phase")
    args = ap.parse_args()

    cache_dir = compilecache.configure()
    device = device_record()
    stats = jax.devices()[0].memory_stats() or {}
    emit(phase="device", **device, bytes_limit=stats.get("bytes_limit"),
         jax=jax.__version__, compile_cache=cache_dir)
    check(device["platform"] != "cpu",
          "device: jax found no accelerator (platform cpu)")
    check(device["count"] == args.chips,
          f"device: {device['count']} devices, --chips {args.chips}")
    if device["platform"] == "cpu" and args.docs is None:
        # no accelerator and no rehearsal size asked for: fail now, not
        # after an hour of the chip's corpus on a CPU
        return finish(device)
    if args.chips == 4:
        run_mesh(args.docs or 40000)
    else:
        run_one_chip(args.docs or 100000, device)
    return finish(device)


if __name__ == "__main__":
    sys.exit(main())
