"""Sharded index + scatter-gather query over a device mesh.

Reference mapping (SURVEY §2.5, §3.2):

* **Document partitioning** — every record routed by docid hash
  (``Hostdb::getShardNum`` ``Hostdb.cpp:2486``; checksum terms by termid,
  ``getShardNumByTermId`` ``Hostdb.cpp:2468``) →
  :class:`ShardedCollection` splits each document's meta list across
  per-shard Collections with the same hash functions.
* **Msg3a scatter-gather** — fan Msg39 out to every shard, k-way merge
  per-shard top-k (``Msg3a.cpp:971``) → one ``shard_map`` over the
  ``shards`` mesh axis: each device scores its own shard's candidates
  (the Msg39 intersect, now :func:`..query.scorer.score_core`), then an
  **in-mesh all-gather top-k merge** replaces the UDP reply + host-side
  merge — the collective rides ICI, and every shard finishes holding the
  replicated global top-k.
* **Msg20 summaries** — per-result titlerec lookups go to the shard
  owning the docid (``Msg20.cpp:90``) → host-side reads from the owning
  shard's titledb.

Per-shard packed shapes are padded to the fleet-wide bucket so the
stacked [S, ...] arrays are rectangular; empty shards ship a zero-valid
dummy block (the reference's empty Msg39 reply).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..build import docproc
from ..index import posdb
from ..index.collection import Collection
from ..index.tagdb import Tagdb
from ..query import weights
from ..query.compiler import QueryPlan, compile_query
from ..query.engine import MAX_PER_SITE, SearchResults, build_results
from ..query.packer import (MAX_POSITIONS, PackedQuery, PreparedQuery,
                            pad_table,
                            _bucket, _pad1, group_flags, pack_pass,
                            prepare_query)
from ..query.scorer import merge_dedup_topk, score_core
from ..utils import devwatch
from ..utils.log import get_logger
from ..utils.membudget import g_membudget
from .hostmap import SHARD_AXIS, HostMap, make_mesh

log = get_logger("parallel")


def _docid_of(url: str) -> int:
    from ..utils import ghash
    from ..utils.url import normalize
    return ghash.doc_id(normalize(url).full)


class ShardedTagdb(Tagdb):
    """Tag records routed by sitehash to their owning shard — the
    reference shards tagdb like any Rdb (``Tagdb.h:323``), and TagRec
    probes each candidate container site on that site's own shard.
    Writes fan out to every twin (Msg1 semantics); reads hit the
    serving replica. The container-walk logic (get_tag / tag_rec /
    site_of / index_gate) is inherited unchanged."""

    def __init__(self, sc: "ShardedCollection"):
        self._sc = sc  # no local Rdb — per-site routing below

    @property
    def empty(self) -> bool:
        return all(c.tagdb.empty for row in self._sc.grid for c in row)

    def _shard_of(self, site: str) -> int:
        return int(self._sc.hostmap.shard_of_site(site))

    def set_tag(self, site: str, name: str, value,
                user: str = "admin") -> None:
        for c in self._sc.replicas_of(self._shard_of(site)):
            c.tagdb.set_tag(site, name, value, user)

    def remove_tag(self, site: str, name: str) -> None:
        for c in self._sc.replicas_of(self._shard_of(site)):
            c.tagdb.remove_tag(site, name)

    def tags_for_site(self, site: str) -> dict[str, object]:
        return self._sc.shards[self._shard_of(site)].tagdb \
            .tags_for_site(site)

    def save(self) -> None:  # per-shard Collections save their own
        pass


class ShardedCollection:
    """One logical collection partitioned across N shards.

    Each shard is a full Collection (posdb/titledb/clusterdb) under
    ``base_dir/shard_XXX/`` — the analog of one gb instance's working dir.
    """

    def __init__(self, name: str, base_dir: str | Path, n_shards: int,
                 n_replicas: int = 1):
        self.name = name
        self.base_dir = Path(base_dir)
        self.hostmap = HostMap(n_shards, n_replicas)
        # grid[s][r]: replica r of shard s — the reference's twins
        # within a shard group (Hostdb "num-mirrors"); replica 0 keeps
        # the unsuffixed directory so single-replica layouts carry over
        self.grid = [
            [Collection(name, self.base_dir /
                        (f"shard_{s:03d}" if r == 0
                         else f"shard_{s:03d}_r{r}"))
             for r in range(n_replicas)]
            for s in range(n_shards)
        ]
        #: monotonic corpus mutation counter (invalidates merged-view
        #: caches even when a replace leaves num_docs unchanged)
        self.mutations = 0
        #: site-routed tag store (bans / boundaries / overrides)
        self.tagdb = ShardedTagdb(self)
        # budget-pressure hook: over-budget reserve() asks us to dump
        # fat memtables across the grid before it refuses (held by
        # weakref, so registration never pins a dead collection)
        g_membudget.add_pressure_handler(self._relieve_memory)

    def _relieve_memory(self, need: int) -> int:
        """Flush the grid's largest memtables until ~``need`` bytes are
        freed (the 'dump the tree' arm of the g_mem gate)."""
        freed = 0
        rdbs = [rdb for row in self.grid for coll in row
                for rdb in coll.rdbs().values()
                if rdb.mem.nbytes >= 1 << 20]
        rdbs.sort(key=lambda r: r.mem.nbytes, reverse=True)
        for rdb in rdbs:
            if freed >= need:
                break
            freed += rdb.mem.nbytes
            rdb.dump()
        if freed:
            log.info("budget pressure: dumped %d MB of memtables",
                     freed >> 20)
        return freed

    @property
    def n_shards(self) -> int:
        return self.hostmap.n_shards

    @property
    def shards(self) -> list[Collection]:
        """Serving replica per shard (Multicast pick-best-twin); falls
        back to replica 0 when the whole shard is dead — reads then
        degrade at the query layer, which checks liveness itself."""
        return [self.grid[s][self.hostmap.serving_replica(s) or 0]
                for s in range(self.n_shards)]

    def replicas_of(self, shard: int) -> list[Collection]:
        """All twins of a shard — the write fan-out set (Msg1 adds go to
        every twin, ``Msg1.cpp:20``)."""
        return self.grid[shard]

    @property
    def num_docs(self) -> int:
        return sum(row[0].num_docs for row in self.grid)

    # --- build plane: route records by shard (Msg4 / Msg1 semantics) ---

    def _linkdb_of(self, site: str):
        """The serving linkdb for a site's records (linkee-site routed,
        like the reference's RDB_LINKDB shard map) — read side."""
        return self.shards[self.hostmap.shard_of_site(site)].linkdb

    def _linkdbs_all(self, site: str):
        """All twins' linkdbs for a site — write fan-out."""
        return [c.linkdb for c in
                self.replicas_of(self.hostmap.shard_of_site(site))]

    def site_num_inlinks(self, site: str) -> int:
        return self._linkdb_of(site).site_num_inlinks(site)

    def index_document(self, url: str, content: str, *, is_html: bool = True,
                       siterank: int = 0, langid: int | None = None,
                       propagate: bool = True):
        """Index one document, scattering its records to owning shards
        (the reference's Msg4 meta-list add: posdb keys split by docid/
        termid shard, titledb+clusterdb to the docid's shard, linkdb
        edges to the linkee site's shard)."""
        from ..utils.url import normalize
        u = normalize(url)
        # tagdb gate (XmlDoc::indexDoc EDOCBANNED + SiteGetter boundary
        # + siterank override) — same semantics as the single-node path
        banned, site, sr_override = self.tagdb.index_gate(u)
        if banned:
            self.remove_document(url, propagate=propagate)
            return None
        if sr_override is not None:
            siterank = sr_override
        self.mutations += 1
        old = self.remove_document(url, propagate=False)
        inlinks = self._linkdb_of(site).inlinks_for_url(site, u.full)
        from ..build.tokenizer import tokenize_html, tokenize_text
        tdoc = (tokenize_html(content, u.full) if is_html
                else tokenize_text(content))
        sect_shard = int(self.hostmap.shard_of_site(site))
        boiler = self.shards[sect_shard].sectiondb.boiler_set(
            site, docproc.doc_section_hashes(tdoc).values())
        ml = docproc.build_meta_list(url, content, is_html=is_html,
                                     siterank=siterank, langid=langid,
                                     inlinks=inlinks, site=site,
                                     site_resolver=self.tagdb.site_of,
                                     tdoc=tdoc, boiler_sections=boiler)
        home = int(self.hostmap.shard_of_docid(ml.docid))
        key_shards = self.hostmap.shard_of_keys(ml.posdb_keys)
        # every record goes to ALL twins of its owning shard (the Msg1
        # twin-add fan-out, Msg1.cpp:20)
        for s in np.unique(key_shards):
            for coll in self.replicas_of(int(s)):
                coll.posdb.add(ml.posdb_keys[key_shards == s])
        for coll in self.replicas_of(home):
            coll.titledb.add(ml.titledb_key.reshape(1), [ml.title_rec])
            coll.clusterdb.add(ml.clusterdb_key.reshape(1))
            if ml.fielddb_keys is not None and len(ml.fielddb_keys):
                coll.fielddb.add(ml.fielddb_keys, ml.fielddb_blobs)
            coll.titlerec_cache.pop(ml.docid, None)
            coll.doc_added()
            if ml.words:
                coll.speller.add_doc_words(ml.words)
        for coll in self.replicas_of(sect_shard):
            coll.sectiondb.add_page_sections(site, u.full, ml.sections)
        # outlink edges → linkee-site shards; refresh affected linkees
        # (shared propagate step, including the old version's linkees)
        edges = ml.edges
        for linkee, anchor in edges:
            lk_site = ml.edge_sites.get(linkee.full, linkee.site)
            for ldb in self._linkdbs_all(lk_site):
                ldb.add_link(
                    lk_site, site, u.full, linkee_url=linkee.full,
                    anchor_text=anchor, linker_siterank=siterank)
        ml.refresh_targets = [e[0] for e in edges]
        if old:
            ml.refresh_targets += old.refresh_targets
        if propagate:
            self._refresh_linkees(ml.refresh_targets, site)
        return ml

    def _refresh_linkees(self, linkees, own_site: str) -> None:
        """Breadth-first anchor propagation (iterative worklist in
        :func:`docproc.refresh_linkees`; each reindex is non-propagating
        and feeds its own affected linkees back into the queue)."""
        from ..spider.linkdb import site_rank
        docproc.refresh_linkees(
            linkees, own_site,
            get_doc=lambda lk: self.get_document(_docid_of(lk.full)),
            linkdb_of=self._linkdb_of,
            reindex=lambda lk, rec: self.index_document(
                lk.full, rec.get("content", rec["text"]),
                is_html=rec.get("is_html", True),
                siterank=site_rank(self.site_num_inlinks(
                    self.tagdb.site_of(lk))),
                langid=rec.get("langid"), propagate=False),
            site_of=self.tagdb.site_of)

    def remove_document(self, url: str, propagate: bool = True):
        from ..spider.linkdb import pack_key as link_key
        from ..utils.url import normalize
        self.mutations += 1
        docid = _docid_of(url)
        home = int(self.hostmap.shard_of_docid(docid))
        ml = docproc.get_document(self.shards[home], url=url)
        if ml is None:
            return None
        # regenerate tombstones and scatter them the same way (all twins)
        dead = docproc.tombstone_meta_list(ml)
        key_shards = self.hostmap.shard_of_keys(dead.posdb_keys)
        for s in np.unique(key_shards):
            for coll in self.replicas_of(int(s)):
                coll.posdb.add(dead.posdb_keys[key_shards == s])
        for coll in self.replicas_of(home):
            coll.titledb.add(dead.titledb_key.reshape(1), [b""])
            coll.clusterdb.add(dead.clusterdb_key.reshape(1))
            if dead.fielddb_keys is not None and len(dead.fielddb_keys):
                coll.fielddb.add(dead.fielddb_keys, dead.fielddb_blobs)
            coll.titlerec_cache.pop(dead.docid, None)
            if dead.words:
                coll.speller.remove_doc_words(dead.words)
            coll.doc_removed()
        u = normalize(url)
        for coll in self.replicas_of(
                int(self.hostmap.shard_of_site(dead.site))):
            coll.sectiondb.remove_page_sections(
                dead.site, u.full, ml.get("sections") or [])
        edges = dead.edges
        for linkee, _anchor in edges:
            # delete under the boundary frozen at add time (titlerec map)
            lk_site = dead.edge_sites.get(linkee.full) \
                or self.tagdb.site_of(linkee)
            if lk_site == dead.site:
                continue
            for ldb in self._linkdbs_all(lk_site):
                ldb.rdb.delete(
                    link_key(lk_site, linkee.full, dead.site,
                             u.full).reshape(1))
        dead.refresh_targets = [e[0] for e in edges]
        if propagate:
            self._refresh_linkees(dead.refresh_targets, dead.site)
        return dead

    def get_document(self, docid: int) -> dict | None:
        """Msg22 titlerec fetch from the owning shard."""
        home = int(self.hostmap.shard_of_docid(docid))
        return docproc.get_document(self.shards[home], docid=docid)

    # --- twin patching / replica resync (Msg5 error correction +
    # recovered-twin catch-up) ------------------------------------------

    def scrub(self) -> dict[str, list[str]]:
        """Integrity sweep over every replica's every Rdb; corrupt runs
        are quarantined and immediately healed from a live twin."""
        report: dict[str, list[str]] = {}
        to_heal: list[tuple[int, int]] = []
        # pass 1: scrub EVERY replica before any resync — healing from
        # a not-yet-scrubbed sibling could install ITS undetected
        # corruption over recoverable state (each twin may hold the
        # good copy of a different Rdb)
        for s in range(self.n_shards):
            for r, coll in enumerate(self.grid[s]):
                for name, rdb in coll.rdbs().items():
                    rdb.scrub()
                # includes runs quarantined at LOAD time — a restarted
                # node with corruption found then still needs the patch
                bad = [f"{name}/{run}"
                       for name, rdb in coll.rdbs().items()
                       for run in rdb.quarantined]
                if bad:
                    report[f"shard{s}_r{r}"] = bad
                    to_heal.append((s, r))
        # pass 2: heal
        for s, r in to_heal:
            self.resync_replica(s, r)
        return report

    def resync_replica(self, shard: int, replica: int) -> bool:
        """Rebuild one twin from a healthy sibling — both the corrupt-
        run patch (``Msg5.h:50`` twin correction) and the recovered-
        dead-twin catch-up the reference performs before letting a host
        rejoin its group. Returns False when no healthy source exists."""
        row = self.grid[shard]
        src = None
        for r, cand in enumerate(row):
            if r != replica and self.hostmap.alive[shard, r]:
                src = cand
                break
        if src is None:
            return False
        dst = row[replica]
        for name, srdb in src.rdbs().items():
            drdb = dst.rdbs()[name]
            drdb.replace_with(srdb.get_all())
        dst.num_docs = src.num_docs
        dst._save_stats()
        from collections import defaultdict
        dst.speller.counts = defaultdict(int, src.speller.counts)
        dst.speller._len_index = None
        dst.titlerec_cache.clear()
        self.mutations += 1
        self.hostmap.mark_alive(shard, replica)
        log.info("resynced shard %d replica %d from a twin", shard,
                 replica)
        return True

    def save(self) -> None:
        for row in self.grid:
            for c in row:
                c.save()


# ---------------------------------------------------------------------------
# the sharded kernel (Msg39 per shard + Msg3a merge, one program)
# ---------------------------------------------------------------------------

def _pad_packed(pq: PackedQuery | None, T: int, L: int, D: int,
                plan: QueryPlan, freqw: np.ndarray) -> PackedQuery:
    """Pad one shard's pack to the fleet-wide (T, L, D) bucket; ``None``
    becomes an all-invalid dummy block (empty Msg39 reply)."""
    fl = plan.filters or plan.sortby is not None
    if pq is None:
        required, negative, scored, counts = group_flags(plan, T)
        return PackedQuery(
            doc_idx=np.full((T, L), D, np.int32),
            payload=np.zeros((T, L), np.uint32),
            slot=np.zeros((T, L), np.int32),
            valid=np.zeros((T, L), bool),
            freq_weight=_pad1(freqw, T, 0.5),
            required=required, negative=negative, scored=scored,
            counts=counts, table=pad_table(plan.bool_table),
            cand_docids=np.empty(0, np.uint64),
            siterank=np.zeros(D, np.int32), doclang=np.zeros(D, np.int32),
            n_docs=0, qlang=plan.lang,
            filt=np.zeros(D, bool) if fl else None,
            sortc=np.zeros(D, np.float32) if fl else None,
            use_filter=bool(plan.filters),
            use_sort=plan.sortby is not None)
    t, l = pq.doc_idx.shape
    d = len(pq.siterank)
    doc_idx = np.full((T, L), D, np.int32)
    # re-point this shard's dump row (== its old D pad) at the new one
    di = pq.doc_idx.copy()
    di[di >= d] = D
    doc_idx[:t, :l] = di
    payload = np.zeros((T, L), np.uint32)
    payload[:t, :l] = pq.payload
    slot = np.zeros((T, L), np.int32)
    slot[:t, :l] = pq.slot
    valid = np.zeros((T, L), bool)
    valid[:t, :l] = pq.valid
    siterank = np.zeros(D, np.int32)
    siterank[:d] = pq.siterank
    doclang = np.zeros(D, np.int32)
    doclang[:d] = pq.doclang
    filt = sortc = None
    if pq.filt is not None or pq.sortc is not None or fl:
        filt = np.zeros(D, bool)
        sortc = np.zeros(D, np.float32)
        if pq.filt is not None:
            filt[: len(pq.filt)] = pq.filt
        if pq.sortc is not None:
            sortc[: len(pq.sortc)] = pq.sortc
    return PackedQuery(
        doc_idx=doc_idx, payload=payload, slot=slot, valid=valid,
        freq_weight=_pad1(freqw, T, 0.5),
        required=pq.required, negative=pq.negative,
        scored=pq.scored, counts=pq.counts, table=pq.table,
        cand_docids=pq.cand_docids,
        siterank=siterank, doclang=doclang, n_docs=pq.n_docs,
        qlang=pq.qlang, filt=filt, sortc=sortc,
        use_filter=pq.use_filter, use_sort=pq.use_sort)


@partial(jax.jit, static_argnames=("mesh", "local_k", "out_k",
                                   "n_positions", "use_filter",
                                   "use_sort"))
def _sharded_score(mesh, doc_idx, payload, slot, valid, freq_weight,
                   required, negative, scored, counts, table, siterank,
                   doclang, qlang,
                   n_docs, filt, sortc, local_k: int, out_k: int,
                   n_positions: int = MAX_POSITIONS,
                   use_filter: bool = False, use_sort: bool = False):
    """shard_map program: per-shard intersect+score, in-mesh top-k merge.

    Inputs carry a leading shard axis [S, ...]; outputs are replicated:
    (total matches, merged scores [out_k], owning shard [out_k],
    local idx [out_k]). ``local_k`` caps each shard's contribution (≤ its
    candidate count); the merge then takes the global ``out_k`` best of
    the S·local_k gathered survivors.
    """
    spec = P(SHARD_AXIS)
    rep = P()

    def per_shard(di, pl, sl, va, fw, rq, ng, sc, ct, tb, sr, dl, ql,
                  nd, ft, so):
        n_matched, ts, ti = score_core(
            di[0], pl[0], sl[0], va[0], fw[0], rq[0], ng[0], sc[0],
            ct[0], tb[0], sr[0], dl[0], ql[0], nd[0],
            n_positions=n_positions, topk=local_k,
            filt=ft[0], sortc=so[0],
            use_filter=use_filter, use_sort=use_sort)
        k = ts.shape[0]
        # Msg3a merge as an ICI collective: gather every shard's top-k,
        # take the global top-k (replicated on all shards)
        g_sc = jax.lax.all_gather(ts, SHARD_AXIS)        # [S, k]
        g_ix = jax.lax.all_gather(ti, SHARD_AXIS)        # [S, k]
        g_nm = jax.lax.all_gather(n_matched, SHARD_AXIS)  # [S]
        flat = g_sc.reshape(-1)
        m_sc, m_pos = jax.lax.top_k(flat, min(out_k, flat.shape[0]))
        m_shard = (m_pos // k).astype(jnp.uint32)
        m_local = g_ix.reshape(-1)[m_pos].astype(jnp.uint32)
        # one packed output vector = one device→host fetch: [total,
        # shard…, local…, bitcast(score)…]
        return jnp.concatenate([
            jnp.atleast_1d(jnp.sum(g_nm).astype(jnp.uint32)),
            m_shard, m_local,
            jax.lax.bitcast_convert_type(m_sc, jnp.uint32),
        ])

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(spec,) * 16,
        out_specs=rep, check_vma=False,
    )(doc_idx, payload, slot, valid, freq_weight, required, negative,
      scored, counts, table, siterank, doclang, qlang, n_docs, filt,
      sortc)


def _global_freq_weights(preps: list[PreparedQuery | None],
                         plan: QueryPlan, num_docs: int) -> np.ndarray:
    """Cluster-wide term-frequency weights: per-shard unique-doc counts
    summed — including shards with no candidates, whose postings still
    count toward document frequency (the reference ships global
    termFreqWeights in the Msg39 request, computed at the Msg3a layer).
    Fully-dead shards (None) can't be counted — degraded stats."""
    counts = sum(p.unique_counts for p in preps if p is not None)
    if isinstance(counts, int):  # every shard down
        counts = np.zeros(len(plan.groups), np.int64)
    return weights.term_freq_weight(counts, max(num_docs, 1))


def sharded_search(sc: ShardedCollection, q: str | QueryPlan, *,
                   mesh=None, topk: int = 10, lang: int = 0,
                   offset: int = 0,
                   with_snippets: bool = True,
                   site_cluster: bool = True) -> SearchResults:
    """Scatter-gather query over the mesh (Msg40→Msg3a→Msg39 path)."""
    plan = q if isinstance(q, QueryPlan) else compile_query(q, lang=lang)
    if mesh is None:
        mesh = make_mesh(sc.n_shards)

    # a shard with NO alive twin contributes nothing — not even term
    # stats; the answer is flagged degraded (the reference surfaces dead
    # hosts on PageHosts; silent partial results are a correctness trap)
    serving = [sc.hostmap.serving_replica(s) for s in range(sc.n_shards)]
    degraded = any(r is None for r in serving)
    # cross-shard sort-key base (gbsortby): every shard shifts by the
    # same minimum or the merged ordering is wrong
    sort_base = None
    if plan.sortby is not None:
        from ..query.packer import local_sort_base
        bases = [b for i, c in enumerate(sc.shards)
                 if serving[i] is not None
                 and (b := local_sort_base(c, *plan.sortby)) is not None]
        sort_base = min(bases) if bases else 0.0
    preps = [prepare_query(c, plan, sort_base=sort_base)
             if serving[i] is not None else None
             for i, c in enumerate(sc.shards)]
    freqw = _global_freq_weights(preps, plan, sc.num_docs)

    # dead shards contribute an empty block: the query degrades instead
    # of failing, like Multicast skipping dead twins (Multicast.cpp:520);
    # with replicas configured the replica's collection serves instead
    packs = [pack_pass(p) if p is not None else None for p in preps]
    live = [p for p in packs if p is not None]
    if not live:
        return SearchResults(query=plan.raw, total_matches=0,
                             degraded=degraded,
                             suggestion=suggest_sharded(sc, plan))
    T = max(p.doc_idx.shape[0] for p in live)
    L = max(p.doc_idx.shape[1] for p in live)
    D = max(len(p.siterank) for p in live)
    packs = [_pad_packed(p, T, L, D, plan, freqw) for p in packs]

    # local_k rides the power-of-two bucket ladder: topk+offset is
    # request-controlled, and _sharded_score takes it as a STATIC, so
    # an unbucketed value would mint one shard_map compile per page
    # size (the Msg39 retrace cliff the jit-unstable-static lint bans)
    k = min(_bucket(max(topk + offset, 64), 64), D)
    stack = lambda f: np.stack([f(p) for p in packs])
    args = dict(
        doc_idx=stack(lambda p: p.doc_idx),
        payload=stack(lambda p: p.payload),
        slot=stack(lambda p: p.slot),
        valid=stack(lambda p: p.valid),
        freq_weight=stack(lambda p: p.freq_weight),
        required=stack(lambda p: p.required),
        negative=stack(lambda p: p.negative),
        scored=stack(lambda p: p.scored),
        counts=stack(lambda p: p.counts),
        table=stack(lambda p: p.table),
        siterank=stack(lambda p: p.siterank),
        doclang=stack(lambda p: p.doclang),
        qlang=np.full(sc.n_shards, plan.lang, np.int32),
        n_docs=stack(lambda p: np.int32(p.n_docs)),
        filt=stack(lambda p: p.filt if p.filt is not None
                   else np.zeros(len(p.siterank), bool)),
        sortc=stack(lambda p: p.sortc if p.sortc is not None
                    else np.zeros(len(p.siterank), np.float32)),
    )
    # lay the shard axis over the mesh so each device holds its own block
    sharded_args = {
        name: jax.device_put(
            a, NamedSharding(mesh, P(SHARD_AXIS,
                                     *([None] * (a.ndim - 1)))))
        for name, a in args.items()
    }
    # over-fetch + escalate: if site clustering leaves the page short,
    # re-merge with a larger out_k (the reference's Msg40 recall loop,
    # Msg40.cpp:2117, redesigned as k·c over-fetch per SURVEY §7 hard
    # part (c) — the per-shard scoring is cached, only the merge regrows)
    from ..query.engine import PQR_SCAN, finish_page
    want = max(topk + offset, PQR_SCAN)
    out_k = max(want, 64)
    max_out = sc.n_shards * k
    while True:
        # out_k is static too — bucket it so the escalation ladder
        # (×4 per round) revisits the same compiled programs
        kk = min(_bucket(out_k, 64), max_out)
        out = np.asarray(_sharded_score(
            mesh, sharded_args["doc_idx"], sharded_args["payload"],
            sharded_args["slot"], sharded_args["valid"],
            sharded_args["freq_weight"], sharded_args["required"],
            sharded_args["negative"], sharded_args["scored"],
            sharded_args["counts"], sharded_args["table"],
            sharded_args["siterank"], sharded_args["doclang"],
            sharded_args["qlang"], sharded_args["n_docs"],
            sharded_args["filt"], sharded_args["sortc"],
            local_k=k, out_k=kk,
            use_filter=bool(plan.filters),
            use_sort=plan.sortby is not None))
        total = int(out[0])
        m_shard = out[1:1 + kk].astype(np.int64)
        m_local = out[1 + kk:1 + 2 * kk].astype(np.int64)
        m_sc = out[1 + 2 * kk:].view(np.float32).copy()

        # map (owning shard, local candidate idx) → docid; padded-slot
        # hits score 0 and are filtered inside build_results
        docids = np.zeros(len(m_sc), np.uint64)
        for i, (shard, local) in enumerate(zip(m_shard, m_local)):
            cd = packs[int(shard)].cand_docids
            if int(local) < len(cd):
                docids[i] = cd[int(local)]
            else:
                m_sc[i] = 0.0
        results, clustered = build_results(
            sc.get_document, docids, m_sc, plan, topk=want,
            with_snippets=False, site_cluster=site_cluster)
        if (len(results) >= want or clustered == 0 or out_k >= max_out):
            break
        out_k *= 4
    from ..query.engine import _coll_langid_of
    page = finish_page(
        results, offset=offset, topk=topk,
        conf=sc.shards[0].conf, qlang=plan.lang,
        get_doc=sc.get_document,
        langid_of=lambda d: _coll_langid_of(
            sc.shards[int(sc.hostmap.shard_of_docid(d))])(d),
        words=plan.match_words(),
        with_snippets=with_snippets)
    return SearchResults(
        query=plan.raw, total_matches=int(total), results=page,
        clustered=clustered, degraded=degraded,
        suggestion=suggest_sharded(sc, plan) if total == 0 else None)


# ---------------------------------------------------------------------------
# mesh-resident serving: the Msg3a merge ON the device (one program/wave)
# ---------------------------------------------------------------------------

def _site_cols(coll: Collection):
    """One shard's clusterdb lookup columns (sorted docids + aligned
    sitehash/langid), cached on the clusterdb Rdb version — pack-time
    candidate sitehash columns become one vectorized searchsorted
    instead of D point reads per query."""
    ver = coll.clusterdb.version
    cached = getattr(coll, "_mesh_site_cols", None)
    if cached is not None and cached[0] == ver:
        return cached[1]
    from ..index import clusterdb as cdb
    lst = coll.clusterdb.get_all()
    if len(lst):
        f = cdb.unpack_key(lst.keys)
        order = np.argsort(f["docid"], kind="stable")
        cols = (f["docid"][order].astype(np.uint64),
                f["sitehash"][order].astype(np.uint32),
                f["langid"][order].astype(np.uint32))
    else:
        cols = (np.empty(0, np.uint64), np.empty(0, np.uint32),
                np.empty(0, np.uint32))
    coll._mesh_site_cols = (ver, cols)
    return cols


def _cand_site_cols(coll: Collection, cand: np.ndarray):
    """Candidate docids → (sitehash, langid) uint32 columns. Duplicate
    clusterdb records per docid keep the LATEST (side='right' − 1, the
    same last-wins rule as ``_coll_langid_of``); missing records map to
    0 — exempt from site clustering, like the host walk."""
    docids, sh, lg = _site_cols(coll)
    out_sh = np.zeros(len(cand), np.uint32)
    out_lg = np.zeros(len(cand), np.uint32)
    if len(docids) and len(cand):
        pos = np.searchsorted(docids, cand, side="right") - 1
        ok = pos >= 0
        ok[ok] = docids[pos[ok]] == cand[ok]
        out_sh[ok] = sh[pos[ok]]
        out_lg[ok] = lg[pos[ok]]
    return out_sh, out_lg


def mesh_generation(sc: ShardedCollection) -> tuple:
    """The mesh serving generation: corpus mutations × read topology ×
    per-serving-twin posdb versions. Any write, twin death (mark_dead)
    or recovery moves this tuple; the ResidentLoop's freshness protocol
    then drains in-flight waves against their issue-time base and packs
    the next wave from the NEW serving twins — which is exactly the
    twin-failover story: a dead chip's shard degrades to its twin's
    base with zero lost queries."""
    serving = sc.hostmap.serving_vector()
    return (sc.mutations, serving,
            tuple(sc.grid[s][r].posdb.version if r is not None else -1
                  for s, r in enumerate(serving)))


@partial(jax.jit, static_argnames=("mesh", "local_k", "out_k",
                                   "n_positions", "use_filter",
                                   "use_sort"))
def _mesh_serve(mesh, doc_idx, payload, slot, valid, freq_weight,
                required, negative, scored, counts, table, siterank,
                doclang, qlang, n_docs, filt, sortc, dochi, doclo,
                shash, n_cand, local_k: int, out_k: int,
                n_positions: int = MAX_POSITIONS,
                use_filter: bool = False, use_sort: bool = False):
    """The mesh-resident serving program: one ``shard_map`` per ticket
    wave doing per-shard intersection + scoring (vmapped over the query
    batch), the in-jit all-gather top-k merge, AND the clusterdb
    2-per-site dedup as over-fetch k·c — no host hop anywhere between
    shard search and merged, deduped top-k.

    Inputs carry [S, B, ...]; ``dochi``/``doclo`` are the split uint32
    halves of each shard's candidate docids and ``shash`` the per-
    candidate sitehash ([S, B, D]), so the merge output needs no host
    (shard, local)→docid resolution. ``n_cand`` [S, B] masks pad rows.
    Output is replicated uint32 [B, 3 + 5·out_k]: per query
    ``[total, n_kept, n_dropped, hi…, lo…, sitehash…, bitcast(score)…,
    cumdrop…]`` with survivors compacted to a score-ordered prefix —
    the final tiny block that crosses at the wave's collect boundary.
    """
    spec = P(SHARD_AXIS)

    def one_query(di, pl, sl, va, fw, rq, ng, sc, ct, tb, sr, dl, ql,
                  nd, ft, so, dh, dlo, sh, nc):
        n_matched, ts, ti = score_core(
            di, pl, sl, va, fw, rq, ng, sc, ct, tb, sr, dl, ql, nd,
            n_positions=n_positions, topk=local_k, filt=ft, sortc=so,
            use_filter=use_filter, use_sort=use_sort)
        # pad-candidate hits (idx ≥ this shard's real count) score 0
        ts = jnp.where(ti < nc, ts, 0.0)
        return (n_matched.astype(jnp.uint32), ts, jnp.take(dh, ti),
                jnp.take(dlo, ti), jnp.take(sh, ti))

    def per_shard(di, pl, sl, va, fw, rq, ng, sc, ct, tb, sr, dl, ql,
                  nd, ft, so, dh, dlo, sh, nc):
        # strip the unit shard axis, run the Msg39 intersect for the
        # whole batch on this shard's chip
        nm, ts, hh, ll, shh = jax.vmap(one_query)(
            di[0], pl[0], sl[0], va[0], fw[0], rq[0], ng[0], sc[0],
            ct[0], tb[0], sr[0], dl[0], ql[0], nd[0], ft[0], so[0],
            dh[0], dlo[0], sh[0], nc[0])
        # Msg3a as an ICI collective: every shard's [B, k] block
        g_nm = jax.lax.all_gather(nm, SHARD_AXIS)    # [S, B]
        g_sc = jax.lax.all_gather(ts, SHARD_AXIS)    # [S, B, k]
        g_hh = jax.lax.all_gather(hh, SHARD_AXIS)
        g_ll = jax.lax.all_gather(ll, SHARD_AXIS)
        g_sh = jax.lax.all_gather(shh, SHARD_AXIS)

        def merge_one(sc_q, hh_q, ll_q, sh_q, nm_q):
            n_kept, n_drop, hi, lo, shq, scq, cum = merge_dedup_topk(
                sc_q, hh_q, ll_q, sh_q, out_k,
                max_per_site=MAX_PER_SITE)
            pad = out_k - scq.shape[0]
            if pad:
                z = jnp.zeros(pad, jnp.uint32)
                hi, lo, shq, cum = (jnp.concatenate([a, z]) for a in
                                    (hi, lo, shq, cum))
                scq = jnp.concatenate([scq, jnp.zeros(pad,
                                                      jnp.float32)])
            # explicit uint32 on the reductions: x64 mode promotes
            # uint32 sums to uint64, which would widen the whole row
            return jnp.concatenate([
                jnp.atleast_1d(jnp.sum(nm_q).astype(jnp.uint32)),
                jnp.atleast_1d(n_kept), jnp.atleast_1d(n_drop),
                hi, lo, shq,
                jax.lax.bitcast_convert_type(scq, jnp.uint32),
                cum]).astype(jnp.uint32)

        return jax.vmap(merge_one, in_axes=(1, 1, 1, 1, 1))(
            g_sc, g_hh, g_ll, g_sh, g_nm)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=(spec,) * 20,
                         out_specs=P(), check_vma=False)(
        doc_idx, payload, slot, valid, freq_weight, required, negative,
        scored, counts, table, siterank, doclang, qlang, n_docs, filt,
        sortc, dochi, doclo, shash, n_cand)


#: query-batch bucket floor (waves pad to the next power of two so the
#: mesh program's B static revisits compiled shapes)
B_FLOOR = 4

#: over-fetch factor c of the in-program recall ladder: the first
#: merge window is k·c so a page's worth of 2-per-site survivors
#: usually exists without escalation (SURVEY §7 hard part (c))
OVERFETCH_C = 2


@dataclass
class _MeshWave:
    """One dispatched mesh program (a sub-wave of a ticket: plans
    sharing the filter/sort statics). ``args`` keeps the staged device
    operands so the recall escalation re-merges WITHOUT re-packing or
    re-staging — only the merge window (``out_k``) regrows."""
    out: object           # replicated device output [B, 3 + 5·out_k]
    args: dict            # sharded device operands
    qidx: list            # plan indices served by this wave
    local_k: int
    out_k: int
    max_out: int
    use_filter: bool
    use_sort: bool
    stage_key: str = ""   # devwatch mesh_stage ledger column ("" = off)


@dataclass
class MeshPending:
    plans: list
    want: int
    waves: list


class MeshServeIndex:
    """The mesh wave engine behind :class:`MeshResident`'s serving
    path — a ResidentLoop-compatible index (duck type: ``issue_batch``
    / ``collect_batch`` / ``_built_version`` + ``sitehash_of`` /
    ``langid_of``) whose issue dispatches ONE ``shard_map`` program
    across all chips per ticket wave.

    The serving replica set and per-twin posdb versions are frozen
    into ``_built_version`` at build; the loop's drain-before-refresh
    protocol swaps in a fresh index (new twins, new corpus) between
    waves, never under one. Needs ≥ n_shards visible devices (CI
    forces 8 host devices via XLA_FLAGS, conftest.py)."""

    def __init__(self, sc: ShardedCollection, mesh=None):
        self.sc = sc
        self.mesh = mesh if mesh is not None else make_mesh(sc.n_shards)
        self._built_version = mesh_generation(sc)
        serving = sc.hostmap.serving_vector()
        #: pack-time read set: the serving twin per shard, None where
        #: the whole shard is down (its block degrades to the empty
        #: Msg39 reply and the answer is flagged degraded)
        self.colls = [sc.grid[s][r] if r is not None else None
                      for s, r in enumerate(serving)]
        self.degraded = any(c is None for c in self.colls)
        self.total_docs = sc.num_docs

    # --- host-side post-processing lookups (Msg20/Msg51 point reads) ---

    def _home(self, docid: int) -> Collection | None:
        return self.colls[int(self.sc.hostmap.shard_of_docid(docid))]

    def sitehash_of(self, docid: int) -> int:
        c = self._home(docid)
        if c is None:
            return 0
        sh, _ = _cand_site_cols(c, np.asarray([docid], np.uint64))
        return int(sh[0])

    def langid_of(self, docid: int) -> int:
        c = self._home(docid)
        if c is None:
            return 0
        _, lg = _cand_site_cols(c, np.asarray([docid], np.uint64))
        return int(lg[0])

    # --- the issue/collect split the ResidentLoop drives ---------------

    def issue_batch(self, queries, topk: int = 64, lang: int = 0
                    ) -> MeshPending:
        """Pack the wave (host), stage it onto the mesh, dispatch the
        program — returns without blocking on device results."""
        plans = [q if isinstance(q, QueryPlan) else
                 compile_query(q, lang=lang) for q in queries]
        want = max(int(topk), 1)
        # sub-waves by the program's filter/sort statics (a mixed
        # ticket still dispatches before any collect)
        groups: dict[tuple, list[int]] = {}
        for i, plan in enumerate(plans):
            key = (bool(plan.filters), plan.sortby is not None)
            groups.setdefault(key, []).append(i)
        waves = []
        for (use_f, use_s), qidx in groups.items():
            wave = self._issue_wave([plans[i] for i in qidx], qidx,
                                    want, use_f, use_s)
            waves.append(wave)
        return MeshPending(plans=plans, want=want, waves=waves)

    def _issue_wave(self, plans, qidx, want, use_f, use_s):
        sc = self.sc
        S = sc.n_shards
        per_q = []      # (packs[s] | None, freqw) per plan
        for plan in plans:
            sort_base = None
            if plan.sortby is not None:
                from ..query.packer import local_sort_base
                bases = [b for c in self.colls if c is not None
                         and (b := local_sort_base(c, *plan.sortby))
                         is not None]
                sort_base = min(bases) if bases else 0.0
            preps = [prepare_query(c, plan, sort_base=sort_base)
                     if c is not None else None for c in self.colls]
            freqw = _global_freq_weights(preps, plan, self.total_docs)
            per_q.append(([pack_pass(p) if p is not None else None
                           for p in preps], freqw))
        live = [p for packs, _ in per_q for p in packs if p is not None]
        if not live:
            return _MeshWave(out=None, args={}, qidx=list(qidx),
                             local_k=0, out_k=0, max_out=0,
                             use_filter=use_f, use_sort=use_s)
        # fleet-wide buckets across the whole wave: rectangular
        # [S, B, ...] stacks, one compiled program per bucket tuple
        T = max(p.doc_idx.shape[0] for p in live)
        L = max(p.doc_idx.shape[1] for p in live)
        D = max(len(p.siterank) for p in live)
        local_k = min(_bucket(max(want, 64), 64), D)
        B = _bucket(max(len(plans), 1), B_FLOOR)
        rows = []   # per padded-query: (packs[s], plan, freqw)
        for (packs, freqw), plan in zip(per_q, plans):
            rows.append(([_pad_packed(p, T, L, D, plan, freqw)
                          for p in packs], plan, freqw))
        while len(rows) < B:    # pad the batch with empty queries
            plan, freqw = plans[0], per_q[0][1]
            rows.append(([_pad_packed(None, T, L, D, plan, freqw)
                          for _ in range(S)], plan, freqw))

        def cand_cols(s, packs):
            cand = packs[s].cand_docids
            hi = np.zeros(D, np.uint32)
            lo = np.zeros(D, np.uint32)
            sh = np.zeros(D, np.uint32)
            d = len(cand)
            if d and self.colls[s] is not None:
                hi[:d] = (cand >> np.uint64(32)).astype(np.uint32)
                lo[:d] = (cand & np.uint64(0xFFFFFFFF)).astype(
                    np.uint32)
                sh[:d], _ = _cand_site_cols(self.colls[s], cand)
            return hi, lo, sh, d

        stack = lambda f: np.stack(
            [np.stack([f(packs[s]) for packs, _, _ in rows])
             for s in range(S)])
        cols = [[cand_cols(s, packs) for packs, _, _ in rows]
                for s in range(S)]
        args = dict(
            doc_idx=stack(lambda p: p.doc_idx),
            payload=stack(lambda p: p.payload),
            slot=stack(lambda p: p.slot),
            valid=stack(lambda p: p.valid),
            freq_weight=stack(lambda p: p.freq_weight),
            required=stack(lambda p: p.required),
            negative=stack(lambda p: p.negative),
            scored=stack(lambda p: p.scored),
            counts=stack(lambda p: p.counts),
            table=stack(lambda p: p.table),
            siterank=stack(lambda p: p.siterank),
            doclang=stack(lambda p: p.doclang),
            qlang=np.stack([np.asarray([plan.lang for _, plan, _
                                        in rows], np.int32)] * S),
            n_docs=stack(lambda p: np.int32(p.n_docs)),
            filt=stack(lambda p: p.filt if p.filt is not None
                       else np.zeros(len(p.siterank), bool)),
            sortc=stack(lambda p: p.sortc if p.sortc is not None
                        else np.zeros(len(p.siterank), np.float32)),
            dochi=np.stack([np.stack([c[0] for c in cs])
                            for cs in cols]),
            doclo=np.stack([np.stack([c[1] for c in cs])
                            for cs in cols]),
            shash=np.stack([np.stack([c[2] for c in cs])
                            for cs in cols]),
            n_cand=np.stack([np.asarray([c[3] for c in cs], np.int32)
                             for cs in cols]),
        )
        sharded_args = {
            name: jax.device_put(
                a, NamedSharding(self.mesh,
                                 P(SHARD_AXIS, *([None] * (a.ndim - 1)))))
            for name, a in args.items()
        }
        max_out = S * local_k
        out_k = min(_bucket(max(OVERFETCH_C * want, 64), 64), max_out)
        wave = _MeshWave(out=None, args=sharded_args, qidx=list(qidx),
                         local_k=local_k, out_k=out_k, max_out=max_out,
                         use_filter=use_f, use_sort=use_s)
        if devwatch.enabled():
            # transient mesh staging in the HBM ledger: the sharded
            # operands live on-chip from dispatch until collect drops
            # the slot (slot keys cycle mod 8 — bounded vocabulary,
            # and in-flight waves never exceed the loop DEPTH)
            self._stage_seq = getattr(self, "_stage_seq", 0) + 1
            wave.stage_key = f"wave{self._stage_seq % 8}"
            devwatch.note_buffer(
                getattr(self.sc, "name", "mesh"), "mesh_stage",
                wave.stage_key,
                int(sum(a.nbytes for a in args.values())))
        wave.out = self._dispatch(wave)
        return wave

    def _dispatch(self, wave: _MeshWave):
        a = wave.args
        return _mesh_serve(
            self.mesh, a["doc_idx"], a["payload"], a["slot"],
            a["valid"], a["freq_weight"], a["required"], a["negative"],
            a["scored"], a["counts"], a["table"], a["siterank"],
            a["doclang"], a["qlang"], a["n_docs"], a["filt"],
            a["sortc"], a["dochi"], a["doclo"], a["shash"],
            a["n_cand"], local_k=wave.local_k, out_k=wave.out_k,
            use_filter=wave.use_filter, use_sort=wave.use_sort)

    def collect_batch(self, pending: MeshPending):
        """Block on the wave's device output; escalate the merge window
        (×4 out_k, same staged operands — the in-program Msg40 recall
        loop) while a query's survivor prefix is short of ``want`` AND
        its window was fully live. One device fetch per round.

        Returns per plan: ``(docids, scores, total_matches, clustered,
        sitehash)`` — survivors only, already site-deduped."""
        want = pending.want
        results: list = [None] * len(pending.plans)
        empty = (np.empty(0, np.uint64), np.empty(0, np.float32), 0, 0,
                 np.empty(0, np.uint32))
        for wave in pending.waves:
            if wave.out is None:        # every shard down
                for qi in wave.qidx:
                    results[qi] = empty
                continue
            device_s = 0.0
            redispatches = 0
            while True:
                # the mesh wave's ONE blessed host sync (the collect
                # boundary — jitwatch BOUNDARY_SITES lists this file)
                t_fetch = time.perf_counter()
                out = np.asarray(jax.device_get(wave.out))  # osselint: ignore[device-sync] — wave collect boundary
                t_got = time.perf_counter()
                device_s += t_got - t_fetch
                K = wave.out_k
                need_more = False
                for row, qi in zip(out, wave.qidx):
                    n_kept = int(row[1])
                    n_drop = int(row[2])
                    if (n_kept < want and n_kept + n_drop >= K
                            and K < wave.max_out):
                        need_more = True
                        break
                if not need_more:
                    break
                wave.out_k = min(_bucket(wave.out_k * 4, 64),
                                 wave.max_out)
                wave.out = self._dispatch(wave)
                redispatches += 1
            if devwatch.enabled():
                devwatch.note_round(
                    coll=getattr(self.sc, "name", "mesh"),
                    kinds="mesh", waves=1, device_s=device_s,
                    bytes_out=int(out.nbytes), out_k=wave.out_k,
                    escalations=redispatches)
                if wave.stage_key:
                    devwatch.drop_buffer(
                        getattr(self.sc, "name", "mesh"),
                        "mesh_stage", wave.stage_key)
            for row, qi in zip(out, wave.qidx):
                total = int(row[0])
                n_kept = int(row[1])
                n_drop = int(row[2])
                hh = row[3:3 + K].astype(np.uint64)
                ll = row[3 + K:3 + 2 * K].astype(np.uint64)
                sh = row[3 + 2 * K:3 + 3 * K].astype(np.uint32)
                scs = row[3 + 3 * K:3 + 4 * K].view(np.float32)
                cum = row[3 + 4 * K:3 + 5 * K]
                # the greedy walk's clustered counter at the page cut:
                # cumdrop is EXCLUSIVE, so survivor want-1 carries the
                # drops the host walk would have counted before its
                # topk-th accept (it breaks at the top of the next
                # iteration, build_results)
                clustered = (n_drop if n_kept < want
                             else int(cum[want - 1]))
                docids = (hh << np.uint64(32)) | ll
                results[qi] = (docids[:n_kept],
                               scs[:n_kept].astype(np.float32),
                               total, clustered, sh[:n_kept])
        return results


class MeshResident:
    """The PRODUCTION resident index on a device mesh: one
    HBM-resident :class:`~..query.devindex.DeviceIndex` per shard,
    PINNED to its own chip — N shards execute their two-phase /
    direct-cube kernels concurrently on N devices (jit dispatches
    follow the committed operands' device; the host thread pool only
    overlaps the dispatch+fetch round trips).

    Two merge seams coexist here, and which one serves is a mode:

    * ``search_batch`` — the HOST merge: each shard routes every query
      adaptively (F1 κ rung vs direct-cube) by ITS OWN term statistics
      and runs its own lossless escalation ladder, a host-driven loop
      per shard — the reference's Msg39 boundary (``Msg39.cpp:74``)
      with Msg3a merging the tiny top-k replies in numpy.
    * ``serve_batch`` — the MESH-RESIDENT path (the production serving
      mode): one :func:`_mesh_serve` ``shard_map`` program per ticket
      wave under a :class:`~..query.resident.ResidentLoop`, with the
      Msg3a merge, the 2-per-site dedup AND the recall over-fetch all
      in-jit — no host hop between shard search and merge; only the
      final [B, k] (docid, score, sitehash) block crosses at the
      wave's collect boundary.

    Cross-shard score comparability holds on both paths because every
    shard plans with CLUSTER-WIDE term frequencies (global dfs), like
    the reference's Msg39Request termFreqWeights.
    """

    def __init__(self, sc: ShardedCollection, devices=None):
        self.sc = sc
        self._devices = devices
        self._indexes = None
        self._indexes_lock = threading.Lock()
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(max(sc.n_shards, 1))
        # cluster-wide df memo (satellite of the mesh-serving PR):
        # key = termid, valid while every shard's resident base stays
        # on the generation the memo was filled under
        self._df_memo: dict[int, int] = {}
        self._df_memo_gen = None
        self._serve_idx: MeshServeIndex | None = None
        self._serve_loop = None

    @property
    def indexes(self):
        """The per-shard resident bases, one pinned to each chip — built
        on first use. The host-merge path (``search_batch``, its df
        memo) reads them; the mesh-resident serving path packs from the
        shards' Rdbs and never does, so a node that serves through it
        pays neither four base builds before its first answer nor four
        resident sets of HBM."""
        with self._indexes_lock:
            if self._indexes is None:
                self._indexes = self._build_indexes()
            return self._indexes

    def _build_indexes(self):
        sc = self.sc
        devices = self._devices
        if devices is None:
            devices = jax.devices()
        if len(devices) < sc.n_shards:
            # fewer chips than shards: wrap (several shards per chip —
            # still correct, just time-shared, and said out loud)
            log.warning("mesh: %d shards wrap onto %d devices",
                        sc.n_shards, len(devices))
            devices = [devices[s % len(devices)]
                       for s in range(sc.n_shards)]
        # per-shard bases via the sanctioned factory (osselint
        # residency-bypass): the mesh plane owns their lifecycle as a
        # unit — MeshResident.stop(), not per-tenant LRU eviction
        from ..query.engine import build_device_index
        return [build_device_index(sc.shards[s], device=devices[s])
                for s in range(sc.n_shards)]

    def refresh(self) -> None:
        for di in self._indexes or ():
            di.refresh()

    def warm(self) -> None:
        list(self._pool.map(lambda di: di.warm_f1(), self.indexes))

    def _global_df(self, termid: int) -> int:
        """Cluster-wide document frequency, memoized per (termid,
        resident-base generation tuple): repeated terms — every wave
        re-plans the same hot query words — pay the S per-shard
        ``_df_of`` walks ONCE per corpus generation instead of per
        plan."""
        gen = tuple(di.df_generation for di in self.indexes)
        if gen != self._df_memo_gen:
            self._df_memo.clear()
            self._df_memo_gen = gen
        df = self._df_memo.get(termid)
        if df is None:
            df = sum(di._df_of(termid) for di in self.indexes)
            self._df_memo[termid] = df
        return df

    def _global_sort_base(self, fld: str, desc: bool) -> float:
        bases = [b for di in self.indexes
                 if (b := di.sort_base_of(fld, desc)) is not None]
        return min(bases) if bases else 0.0

    def search_batch(self, queries, topk: int = 10, lang: int = 0,
                     offset: int = 0, with_snippets: bool = True,
                     site_cluster: bool = True) -> list[SearchResults]:
        """B queries × S shards: per-shard resident kernels run
        concurrently (different chips), then the Msg3a merge + the
        shared Msg40 tail per query."""
        from ..query.engine import PQR_SCAN, finish_page
        sc = self.sc
        plans = [q if isinstance(q, QueryPlan) else
                 compile_query(q, lang=lang) for q in queries]
        total_docs = sc.num_docs
        want = max(topk + offset, PQR_SCAN)
        k_shard = max(want * 2, 64)

        def run_shard(di):
            return di.search_batch(
                plans, topk=k_shard, lang=lang,
                df_of=self._global_df, total_docs=total_docs,
                sort_base_of=self._global_sort_base)

        per_shard = list(self._pool.map(run_shard, self.indexes))

        out = []
        for qi, plan in enumerate(plans):
            docids = np.concatenate(
                [per_shard[s][qi][0] for s in range(sc.n_shards)])
            scores = np.concatenate(
                [per_shard[s][qi][1] for s in range(sc.n_shards)])
            total = sum(int(per_shard[s][qi][2])
                        for s in range(sc.n_shards))
            order = np.argsort(-scores, kind="stable")

            def site_of(docid, _sc=sc):
                home = int(_sc.hostmap.shard_of_docid(docid))
                return self.indexes[home].sitehash_of(docid)

            results, clustered = build_results(
                sc.get_document, docids[order], scores[order], plan,
                topk=want, with_snippets=False,
                site_cluster=site_cluster, site_of=site_of)
            page = finish_page(
                results, offset=offset, topk=topk,
                conf=sc.shards[0].conf, qlang=plan.lang,
                langid_of=lambda d: self.indexes[
                    int(sc.hostmap.shard_of_docid(d))].langid_of(d),
                get_doc=sc.get_document,
                words=plan.match_words(),
                with_snippets=with_snippets)
            from ..query.engine import compute_facets
            out.append(SearchResults(
                query=plan.raw, total_matches=total, results=page,
                clustered=clustered,
                suggestion=suggest_sharded(sc, plan)
                if total == 0 else None,
                facets=compute_facets(plan, docids[order],
                                      sc.get_document)))
        return out

    def search(self, q, **kw) -> SearchResults:
        return self.search_batch([q], **kw)[0]

    # --- the mesh-resident serving path (in-jit Msg3a merge) -----------

    def _serve_index(self) -> MeshServeIndex:
        """Fresh-or-cached :class:`MeshServeIndex` for the CURRENT mesh
        generation — the ResidentLoop's ``di_fn``. A write or a twin
        death moves :func:`mesh_generation`; the loop drains in-flight
        waves first, then this hands it an index packing from the new
        serving twins."""
        idx = self._serve_idx
        if idx is None or idx._built_version != mesh_generation(self.sc):
            idx = MeshServeIndex(self.sc)
            self._serve_idx = idx
        return idx

    def serve_loop(self):
        """The mesh ResidentLoop, spawned lazily (and respawned if
        stopped) — one ticket wave dispatches one mesh program across
        all chips."""
        from ..query.engine import spawn_resident_loop
        loop = self._serve_loop
        if loop is not None and loop.alive:
            return loop
        loop = spawn_resident_loop(
            self._serve_index,
            gen_fn=lambda: mesh_generation(self.sc),
            name=f"mesh-{self.sc.name}")
        self._serve_loop = loop
        return loop

    def serve_batch(self, queries, topk: int = 10, lang: int = 0,
                    offset: int = 0, with_snippets: bool = True,
                    site_cluster: bool = True,
                    results_lock=None) -> list[SearchResults]:
        """The mesh-resident serving path: submit one ticket, get back
        already-merged, already-site-deduped survivors (plus the
        program's clustered counter), run only the shared Msg40 tail
        (summaries/PQR/facets) on the host.

        ``site_cluster=False`` has no in-program variant (the dedup is
        part of the compiled merge) — it routes through the host-merge
        ``search_batch``. ``results_lock`` guards ONLY the host
        post-processing, like ``search_device_batch``."""
        if not site_cluster:
            return self.search_batch(queries, topk=topk, lang=lang,
                                     offset=offset,
                                     with_snippets=with_snippets,
                                     site_cluster=False)
        import contextlib
        from ..query.engine import (PQR_SCAN, compute_facets,
                                    finish_page)
        sc = self.sc
        plans = [q if isinstance(q, QueryPlan) else
                 compile_query(q, lang=lang) for q in queries]
        want = max(topk + offset, PQR_SCAN)
        ticket = self.serve_loop().submit(plans, topk=want, lang=lang)
        raw = ticket.wait()
        msi = ticket.di     # the index the wave actually ran against
        out = []
        lock_ctx = results_lock if results_lock is not None \
            else contextlib.nullcontext()
        with lock_ctx:
            for plan, (docids, scores, total, clustered, shash) in \
                    zip(plans, raw):
                site_map = {int(d): int(h)
                            for d, h in zip(docids, shash)}
                # survivors are already ≤ MAX_PER_SITE per site; the
                # host walk re-counts only drops the program cannot
                # see (content-hash dedup freeing a site slot)
                results, host_cl = build_results(
                    sc.get_document, docids, scores, plan, topk=want,
                    with_snippets=False, site_cluster=True,
                    site_of=lambda d: site_map.get(int(d), 0))
                page = finish_page(
                    results, offset=offset, topk=topk,
                    conf=sc.shards[0].conf, qlang=plan.lang,
                    langid_of=msi.langid_of, get_doc=sc.get_document,
                    words=plan.match_words(),
                    with_snippets=with_snippets)
                out.append(SearchResults(
                    query=plan.raw, total_matches=total, results=page,
                    clustered=clustered + host_cl,
                    degraded=msi.degraded,
                    suggestion=suggest_sharded(sc, plan)
                    if total == 0 else None,
                    facets=compute_facets(plan, docids,
                                          sc.get_document)))
        return out

    def serve(self, q, **kw) -> SearchResults:
        return self.serve_batch([q], **kw)[0]

    def stop(self) -> None:
        """Tear down the serving loop + shard pool (server shutdown)."""
        if self._serve_loop is not None:
            self._serve_loop.stop()
        self._pool.shutdown(wait=False)


def suggest_sharded(sc: ShardedCollection, plan: QueryPlan) -> str | None:
    """Cluster-wide "did you mean": per-shard popularity dictionaries
    merged so a word common on ONE shard is not misdiagnosed as a typo
    (the reference's Speller dict is host-global; ours shards with the
    docs, so the Msg3a layer merges counts). The merged view is cached
    per topology+corpus version — zero-result queries must stay cheap."""
    from ..query.speller import merged
    words = [g.display for g in plan.scored_groups
             if " " not in g.display and ":" not in g.display]
    if not words:
        return None
    serving = [(s, r) for s in range(sc.n_shards)
               if (r := sc.hostmap.serving_replica(s)) is not None]
    if not serving:
        return None
    live = [sc.grid[s][r].speller for s, r in serving]
    # key on the serving (shard, replica) topology, not id(speller):
    # CPython reuses addresses, so a dead speller's id can alias a
    # fresh one and serve a stale merged dictionary
    key = (sc.mutations, tuple(serving))
    cached = getattr(sc, "_merged_speller", None)
    if cached is None or cached[0] != key:
        cached = (key, merged(live))
        sc._merged_speller = cached
    return cached[1].suggest_query(words)
