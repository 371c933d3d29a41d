"""Search engine front end — query in, ranked results out.

Reference: ``Msg40::getResults`` (``Msg40.cpp:171``) orchestrates
Msg3a (docid ranking fan-out) then Msg20s (per-result title/summary); here
the single-shard path is compile → pack → device score → titledb lookup.
The mesh fan-out (Msg3a/shard_map) layers on top in ``parallel/``.

Docid-range multipass (``Msg39.cpp:277-305`` "docid range splitting"): when
the candidate set exceeds ``max_docs_per_pass``, the engine runs the kernel
over candidate slices and merges top-k across passes — bounding device
memory exactly like the reference bounds RAM.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from ..build import docproc
from ..index.collection import Collection
from ..utils import deadline as deadline_mod
from ..utils import trace
from ..utils.log import get_logger
from ..utils.stats import g_stats
from .compiler import QueryPlan, compile_query
from .packer import pack_pass, prepare_query
from .scorer import run_query

log = get_logger("query")

#: guards first-time creation of a collection's device-index lock
import threading as _threading  # noqa: E402

_DI_CREATE_LOCK = _threading.Lock()

#: compiled-plan cache: compile_query is pure in (raw, lang) and
#: QueryPlan is immutable after compile, so plans never invalidate —
#: no generation, just TTL/LRU bounds (Query.cpp reparsed every time;
#: we don't have to)
from ..cache import g_cacheplane as _g_cacheplane  # noqa: E402

_compiled_cache = _g_cacheplane.register(
    "query.compiled", ttl_s=600.0, max_entries=4096,
    desc="compiled QueryPlans, pure in (raw, lang)")


def _compile_cached(q: str, lang: int) -> QueryPlan:
    ck = (q, lang)
    hit, plan = _compiled_cache.lookup(ck)
    if not hit:
        plan = compile_query(q, lang=lang)
        _compiled_cache.put(ck, plan)
    return plan


#: site-clustering cap: at most this many results per site
#: (reference Msg51/Msg40 "site clustering (max 2/site)", Msg51.h:96)
MAX_PER_SITE = 2


@dataclass
class Result:
    docid: int
    score: float
    url: str = ""
    title: str = ""
    snippet: str = ""
    site: str = ""


@dataclass
class SearchResults:
    query: str
    total_matches: int
    results: list[Result] = field(default_factory=list)
    clustered: int = 0  # results hidden by site clustering (Msg51)
    suggestion: str | None = None  # "did you mean" (Speller)
    #: gbfacet: results — field → [(value, count)], counted over a
    #: SAMPLE of the best-matching docs (the reference likewise
    #: accumulates facets over the result sample, Msg40/PageResults)
    facets: dict = field(default_factory=dict)
    #: True when a whole shard (every twin) was down and its documents
    #: are missing from this answer — the reference surfaces this on
    #: PageHosts; silent partial results are a correctness trap
    degraded: bool = False


#: PostQueryRerank window: only the top PQR_SCAN merged results are
#: reranked (reference m_pqr_docsToScan) — the window is FIXED by rank,
#: not by the requested page, so pagination stays consistent: every
#: page request reranks the same top-48 and slices its own rows out
PQR_SCAN = 48


def pqr_window(conf=None) -> int:
    """Ranks the PostQueryRerank reads: PQR_SCAN, none where the
    collection's ``pqr_enabled`` is off (no conf in reach — the
    cluster client — reranks with the defaults)."""
    return 0 if conf is not None and not conf.pqr_enabled else PQR_SCAN


def build_results(get_doc, docids, scores, plan: QueryPlan, *,
                  topk: int, with_snippets: bool = True,
                  site_cluster: bool = True,
                  dedup_content: bool = True,
                  site_of=None, site_col=None,
                  page: tuple[int, int] | None = None,
                  conf=None) -> tuple[list[Result], int]:
    """Msg40's post-merge stage: walk merged candidates best-first, fetch
    titlerecs from the owning store (Msg20/Msg22), apply content-hash
    dedup (Msg40's checksum dedup of identical pages) and site clustering
    (Msg51: at most MAX_PER_SITE per site, rest hidden), build summaries.

    ``get_doc`` is docid → titlerec dict (routes to the owning shard in
    the mesh path). Returns (results, number hidden by cluster/dedup).

    Clusterdb's sitehash column backs the clustering where the caller
    has it: ``site_col`` (the hashes of ``docids``, row for row —
    :func:`site_column`) or ``site_of`` (docid → hash, asked once a
    row); without either the titlerec's site string does.

    ``page`` = (offset, n): the rendered page window. When given (and
    the column is there), a row costs a titledb read only where the
    answer uses the record: inside ``conf``'s rerank window
    (:func:`pqr_window`), inside the page, or ahead of the page within
    PQR_SCAN, where content-hash dedup decides what the page shows.
    Every other row exists solely to hold a rank, so it carries
    docid+score only: content-hash dedup needs the titlerec and is
    skipped for such gap rows (site clustering is not: the sitehash
    column works without a fetch)."""
    from . import summary as summary_mod

    words = plan.match_words()
    has_col = site_of is not None or site_col is not None
    by_col = site_cluster and has_col
    fetch_to = None  # rows from this rank on, the page apart, are gaps
    if page is not None and has_col:
        page_end = page[0] + page[1]
        fetch_to = max(pqr_window(conf), min(page_end, PQR_SCAN))
    gaps = 0
    per_site: dict = {}
    seen_hashes: set[int] = set()
    results: list[Result] = []
    clustered = 0
    for i, (docid, score) in enumerate(zip(docids, scores)):
        if len(results) >= topk:
            break
        if score <= 0.0:
            continue
        sh = 0
        if by_col:
            # clusterdb-driven clustering (Msg51.h:96): the sitehash
            # column decides BEFORE any titledb fetch, so hidden
            # results never decompress a titlerec
            sh = site_col[i] if site_col is not None \
                else site_of(int(docid))
            if sh and per_site.get(sh, 0) >= MAX_PER_SITE:
                clustered += 1
                continue
        rank = len(results)
        if fetch_to is not None and rank >= fetch_to \
                and not (page[0] <= rank < page_end):
            # gap row: never reranked, never rendered, behind the dedup
            # prefix — skip the titledb fetch entirely
            if sh:
                per_site[sh] = per_site.get(sh, 0) + 1
            results.append(Result(docid=int(docid), score=float(score)))
            gaps += 1
            continue
        rec = get_doc(int(docid))
        r = Result(docid=int(docid), score=float(score))
        if rec:
            r.url = rec.get("url", "")
            # Title.cpp fallback chain: title → h1 → anchor → url
            r.title = summary_mod.choose_title(rec)
            r.site = rec.get("site", "")
            ch = rec.get("content_hash")
            if dedup_content and ch is not None:
                if ch in seen_hashes:
                    clustered += 1
                    continue
                seen_hashes.add(ch)
            if by_col:
                if sh:
                    per_site[sh] = per_site.get(sh, 0) + 1
            elif site_cluster and r.site:
                seen = per_site.get(r.site, 0)
                if seen >= MAX_PER_SITE:
                    clustered += 1
                    continue
                per_site[r.site] = seen + 1
        if rec and with_snippets:
            r.snippet = summary_mod.make_summary(
                rec.get("text", ""), words,
                description=rec.get("meta_description", ""))
        results.append(r)
    if gaps:
        g_stats.count("query.gap_row", gaps)
    return results, clustered


def site_column(di, docids) -> list[int]:
    """Clusterdb's sitehash of every docid at once (0 where the index
    holds none): one search of the docid column a query where
    ``DeviceIndex.sitehash_of`` makes one a row. It stands here and not
    beside ``sitehash_of`` because a line moved above ``_costed`` in
    devindex.py moves the wave programs' compile-cache keys."""
    sh, _ = di._cluster_cols()
    rows, ok = di._docid_pos(np.asarray(docids, np.uint64))
    out = np.zeros(len(rows), np.int64)
    out[ok] = sh[rows[ok]]
    return out.tolist()


def apply_pqr(results, conf=None, qlang: int = 0, langid_of=None) -> None:
    """PostQueryRerank over one result window (PostQueryRerank.cpp
    role; factors from the collection conf, defaults when no conf is
    in reach — the cluster client)."""
    from .rerank import post_query_rerank
    if not pqr_window(conf):
        return
    kw = {}
    if conf is not None:
        kw = dict(lang_demote=conf.pqr_lang_demote,
                  site_demote=conf.pqr_site_demote,
                  depth_demote=conf.pqr_depth_demote)
    window = results[:PQR_SCAN]
    post_query_rerank(window, qlang, langid_of=langid_of, **kw)
    results[:PQR_SCAN] = window


def _coll_langid_of(coll: Collection):
    """Docid → langid via a clusterdb point read (host path analog of
    DeviceIndex.langid_of — same records, so flat/resident parity
    holds under the PQR language rule)."""
    from ..index import clusterdb as cdb
    from ..index import titledb

    def f(docid: int) -> int:
        lst = coll.clusterdb.get_list(titledb.start_key(docid),
                                      titledb.end_key(docid))
        if not len(lst):
            return 0
        return int(cdb.unpack_key(lst.keys)["langid"][-1])
    return f


def finish_page(results, *, offset: int, topk: int, conf=None,
                qlang: int = 0, langid_of=None, get_doc=None,
                words=None, with_snippets: bool = True):
    """The shared post-merge tail every search path runs: PQR over the
    fixed top window → slice the requested page → build summaries for
    the page rows only (deep pages must not pay snippets for the rows
    they skip)."""
    from . import summary as summary_mod
    with trace.timed_span("query.rerank", window=min(len(results),
                                                     PQR_SCAN)):
        apply_pqr(results, conf, qlang, langid_of=langid_of)
    page = results[offset:offset + topk]
    if with_snippets and get_doc is not None:
        with trace.timed_span("query.summary", rows=len(page)):
            for r in page:
                if not r.snippet:
                    rec = get_doc(int(r.docid))
                    if rec:
                        r.snippet = summary_mod.make_summary(
                            rec.get("text", ""), words or [],
                            description=rec.get("meta_description", ""))
    return page


def search(coll: Collection, q: str | QueryPlan, *, topk: int = 10,
           lang: int = 0, max_docs_per_pass: int = 1 << 16,
           with_snippets: bool = True,
           site_cluster: bool = True, offset: int = 0) -> SearchResults:
    """Execute a query against one collection (single shard).
    ``offset`` = deep-paging start row (reference ``s=``)."""
    plan = q if isinstance(q, QueryPlan) else _compile_cached(q, lang)
    raw = plan.raw

    g_stats.count("query")
    with trace.timed_span("query.prepare", q=raw):
        prep = prepare_query(coll, plan)

    # over-fetch + escalate: when site clustering leaves the page short,
    # re-score with a larger k (the Msg40 recall loop, Msg40.cpp:2117,
    # as over-fetch per SURVEY §7 hard part (c)); the sharded path has
    # the same loop around its merge
    want = max(topk + offset, PQR_SCAN)
    k = max(want, 64)
    while True:
        # docid-range multipass: fetch+intersect once, then score
        # candidate slices, merging top-k across passes
        all_docids: list[np.ndarray] = []
        all_scores: list[np.ndarray] = []
        total = 0
        # advance by pq.n_docs, not the requested stride: under memory
        # pressure pack_pass shrinks a pass (budget_shrink) and a fixed
        # stride would silently skip the unshrunk remainder
        doc_off = 0
        npass = 0
        while doc_off < len(prep.cand):
            with trace.timed_span("query.pack", npass=npass,
                                  doc_off=doc_off):
                pq = pack_pass(prep, doc_offset=doc_off,
                               max_docs=max_docs_per_pass,
                               budget_shrink=True)
            if pq is None:
                break
            with trace.timed_span("query.score", npass=npass,
                                  n_docs=pq.n_docs):
                docids, scores, n_matched = run_query(pq, topk=k)
            npass += 1
            total += n_matched
            all_docids.append(docids)
            all_scores.append(scores)
            doc_off += pq.n_docs

        if not all_docids:
            return SearchResults(query=raw, total_matches=0,
                                 suggestion=_suggest(coll, plan))
        docids = np.concatenate(all_docids)
        scores = np.concatenate(all_scores)
        order = np.argsort(-scores, kind="stable")

        with trace.timed_span("query.results"):
            results, clustered = build_results(
                lambda d: docproc.get_document(coll, docid=d),
                docids[order], scores[order], plan, topk=want,
                with_snippets=False, site_cluster=site_cluster)
        if (len(results) >= want or clustered == 0
                or k >= len(prep.cand)):
            break
        k *= 4
    page = finish_page(
        results, offset=offset, topk=topk, conf=coll.conf,
        qlang=plan.lang, langid_of=_coll_langid_of(coll),
        get_doc=lambda d: docproc.get_document(coll, docid=d),
        words=plan.match_words(),
        with_snippets=with_snippets)
    return SearchResults(
        query=raw, total_matches=total, results=page,
        clustered=clustered,
        suggestion=_suggest(coll, plan) if total == 0 else None,
        facets=compute_facets(
            plan, docids[order],
            lambda d: docproc.get_document(coll, docid=d)))


#: facet sample size: facet counts come from the stored fields of the
#: top FACET_SAMPLE matched docs (reference Msg40 samples its results)
FACET_SAMPLE = 256


def compute_facets(plan: QueryPlan, docids, get_doc) -> dict:
    """field → [(value, count)] over a sample of matched docs."""
    if not plan.facets:
        return {}
    from collections import Counter
    counters = {f: Counter() for f in plan.facets}
    for d in list(docids)[:FACET_SAMPLE]:
        rec = get_doc(int(d))
        flds = (rec or {}).get("fields") or {}
        for f in plan.facets:
            if f in flds:
                counters[f][flds[f]] += 1
    return {f: c.most_common(16) for f, c in counters.items()}


def _suggest(coll: Collection, plan: QueryPlan) -> str | None:
    """Zero-result fallback: Speller "did you mean" over the query's
    scored words (reference Msg40 spell-check integration)."""
    words = [g.display for g in plan.scored_groups
             if " " not in g.display and ":" not in g.display]
    return coll.speller.suggest_query(words) if words else None


def get_device_index(coll: Collection):
    """The collection's HBM-resident index, built lazily and refreshed
    when the Rdb version moves (cached on the Collection object).

    A run-set move (dump/merge) triggers an O(corpus) base rebuild —
    the reference's RdbDump/RdbMerge never block the loop
    (``RdbDump.h:21``), and neither does this: the rebuild runs in a
    BACKGROUND thread against a fresh DeviceIndex while the old one
    keeps serving its pre-dump view (frozen — bounded staleness for
    the rebuild's duration), then swaps in atomically. Memtable-only
    changes refresh synchronously (O(memtable)). When the HBM can't
    hold two resident sets (big shards), the swap degrades to a
    blocking rebuild rather than an OOM."""
    import threading

    from .devindex import DeviceIndex
    lock = getattr(coll, "_di_lock", None)
    if lock is None:
        with _DI_CREATE_LOCK:
            lock = getattr(coll, "_di_lock", None)
            if lock is None:
                lock = coll._di_lock = threading.Lock()
    di = getattr(coll, "_device_index", None)
    if di is None:
        with lock:
            di = getattr(coll, "_device_index", None)
            if di is None:
                di = DeviceIndex(coll)
                # pay the cold-plan spike (a first devindex.plan took
                # over a second) at build time, not on the first user
                # query
                di.warm_plans()
                coll._device_index = di
        return di

    rdb = coll.posdb
    if rdb.version == di._built_version:
        return di
    fp = tuple((r.path.name, len(r), r.meta.get("keys_crc"))
               for r in rdb.runs)
    if fp == di._base_fp:
        with lock:  # concurrent /search threads must not both mutate
            di.refresh()  # delta-only: O(memtable), synchronous
        return di
    # run set moved → full rebuild. Double-residency check: old + new
    # device arrays must both fit while the swap is in flight.
    if 2 * di.resident_bytes() + (2 << 30) > (14 << 30):
        with lock:
            di.refresh()  # blocking rebuild — two sets would OOM
        return di
    with lock:
        if getattr(coll, "_di_rebuilding", False):
            return di  # a rebuild is in flight: serve the old view

        def _rebuild():
            try:
                fresh = DeviceIndex(coll)
                fresh.warm_plans()  # before the swap: first query on
                # the fresh index must not re-pay the cold-plan spike
                if di._f1_warmed:
                    fresh.warm_f1()  # ... nor, under a server that
                    # warmed the old one, meet a column bucket that
                    # moved with the rebuild
                with lock:
                    coll._device_index = fresh
            except Exception:  # noqa: BLE001 — keep serving the old
                log.exception("background device rebuild failed")
            finally:
                with lock:
                    coll._di_rebuilding = False

        coll._di_rebuilding = True
        from ..utils import threads as _threads
        _threads.spawn("devindex-rebuild", _rebuild)
    return di


def get_resident_loop(coll: Collection, deadline=None,
                      warm: bool = False):
    """The collection's ResidentLoop — owned by the tenant plane's
    :class:`~..serve.tenancy.ResidencyManager` (LRU hot set, parked
    cold tenants, single-flight cold start). The lazy import mirrors
    get_mesh_resident's: the serve layer imports this module at load,
    so the reverse edge resolves at call time only."""
    from ..serve.tenancy import g_residency
    return g_residency.loop_for(coll, deadline=deadline, warm=warm)


def build_device_index(coll, device=None):
    """Sanctioned DeviceIndex factory for the planes that legitimately
    own per-shard bases (the mesh plane's MeshServeIndex). Everything
    else goes through the residency manager — the osselint
    ``residency-bypass`` rule fences direct construction into
    serve/tenancy.py and this module."""
    from .devindex import DeviceIndex
    return DeviceIndex(coll, device=device)


def spawn_resident_loop(di_fn, gen_fn, **kw):
    """Sanctioned ResidentLoop factory (see build_device_index)."""
    from .resident import ResidentLoop
    return ResidentLoop(di_fn, gen_fn=gen_fn, **kw)


def get_mesh_resident(sc):
    """The ShardedCollection's :class:`~..parallel.sharded.MeshResident`
    (mesh-resident serving: per-shard HBM bases + the in-jit Msg3a
    merge under a ResidentLoop), created lazily like the flat device
    index. Imported lazily — parallel.sharded imports this module at
    load."""
    from ..parallel.sharded import MeshResident
    mr = getattr(sc, "_mesh_resident", None)
    if mr is not None:
        return mr
    with _DI_CREATE_LOCK:
        mr = getattr(sc, "_mesh_resident", None)
        if mr is None:
            mr = MeshResident(sc)
            sc._mesh_resident = mr
    return mr


def search_device_batch(coll: Collection, queries, *, topk: int = 10,
                        lang: int = 0, with_snippets: bool = True,
                        site_cluster: bool = True, offset: int = 0,
                        resident: bool = False, results_lock=None
                        ) -> list[SearchResults]:
    """Batched resident-index search: B queries in one device round trip
    (the TPU throughput mode — vmap over queries, SURVEY §7.8).

    ``resident=True`` routes the device work through the collection's
    ResidentLoop: the dispatch is an enqueue onto a loop that is
    already double-buffering waves, not a fresh issue→block round trip.
    ``results_lock``, when given, is held ONLY around the host
    post-processing (titledb reads mutate rdblite state), once a batch
    and whole: never around the submit or the device wait. That alone
    overlaps nothing: the tails of one lock run one after another, so
    batch N's wave runs under batch N-1's tail only where the caller
    keeps more batches out than are in their tails at once (the
    server's ``QueryBatcher``: ``2 * resident.DEPTH``)."""
    import contextlib
    plans = [q if isinstance(q, QueryPlan) else _compile_cached(q, lang)
             for q in queries]
    g_stats.count("query", len(plans))
    ktot = max((topk + offset) * 2, 64)
    if deadline_mod.check_abandon("device.dispatch"):
        # the coordinator timed out while this batch queued — abandon
        # before the device wave, not after it
        raise deadline_mod.DeadlineExceeded(
            "deadline exceeded before device dispatch")
    if resident:
        loop = get_resident_loop(coll, deadline=deadline_mod.current())
        with trace.timed_span("query.device_batch", queries=len(plans),
                              topk=ktot, resident=True):
            ticket = loop.submit(plans, topk=ktot, lang=lang,
                                 deadline=deadline_mod.current())
            raw = ticket.wait()
        di = ticket.di  # the index the wave actually ran against
    else:
        di = get_device_index(coll)
        with trace.timed_span("query.device_batch", queries=len(plans),
                              topk=ktot):
            raw = di.search_batch(plans, topk=ktot, lang=lang)

    # one titlerec memo for the whole batch: build_results, PQR,
    # page snippets and facets all re-read the same top docids
    doc_memo: dict[int, dict | None] = {}
    fetches = 0

    def get_doc(d: int):
        nonlocal fetches
        d = int(d)
        if d in doc_memo:
            return doc_memo[d]
        if len(doc_memo) >= 4096:
            doc_memo.clear()
        fetches += 1
        rec = docproc.get_document(coll, docid=d)
        doc_memo[d] = rec
        return rec

    out = []
    lock_ctx = results_lock if results_lock is not None \
        else contextlib.nullcontext()
    # the container keeps its start (lock wait included) and is a span
    # on the profiler's clock; its two parts, cut at one clock reading,
    # are the request's stages
    batch_span = trace.timed_span("query.results_batch",
                                  queries=len(plans))
    with batch_span, lock_ctx:
        t_held, cpu_held = time.perf_counter(), time.thread_time()
        for plan, (docids, scores, n_matched) in zip(plans, raw):
            results, clustered = build_results(
                get_doc,
                docids, scores, plan, topk=max(topk + offset, PQR_SCAN),
                with_snippets=False, site_cluster=site_cluster,
                site_col=site_column(di, docids), page=(offset, topk),
                conf=coll.conf)
            page = finish_page(
                results, offset=offset, topk=topk, conf=coll.conf,
                qlang=plan.lang, langid_of=di.langid_of,
                get_doc=get_doc,
                words=plan.match_words(),
                with_snippets=with_snippets)
            out.append(SearchResults(
                query=plan.raw, total_matches=n_matched, results=page,
                clustered=clustered,
                suggestion=_suggest(coll, plan)
                if n_matched == 0 else None,
                facets=compute_facets(plan, docids, get_doc)))
    g_stats.count("query.titlerec_fetch", fetches)
    if results_lock is not None:
        trace.record("query.lock_wait", batch_span.t0, t_held)
    trace.record("query.results_work", t_held, batch_span.t1,
                 cpu0=cpu_held, queries=len(out))
    return out


def search_device(coll: Collection, q, **kw) -> SearchResults:
    """Single-query resident-index search (one RPC up, one down)."""
    return search_device_batch(coll, [q], **kw)[0]
