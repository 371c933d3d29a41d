"""Document indexer — tokens → database records (the XmlDoc equivalent).

Reference: ``XmlDoc::indexDoc`` (``XmlDoc.cpp:2455``) → ``getMetaList``
(``XmlDoc.cpp:23825``) assembles every database's records for one document:
posdb postings via ``hashAll`` (``XmlDoc.cpp:28957``), the compressed
TitleRec (``XmlDoc.cpp:5385``), the clusterdb record, spiderdb outlink
requests and linkdb records; deletion/reindex regenerates the *old*
document's meta list with tombstone keys.

TPU-first: instead of a 200-stage callback DAG, one straight-line function
computes columnar token arrays, vectorized rank vectors, and a single
batched ``pack`` per database.

Rank semantics (kept faithful so scoring matches):

* density rank = ``MAXDENSITYRANK - (alnum words in sentence - 1)``,
  clamped to ≥1; whole-string count for title/meta/inlink groups
  (reference ``getDensityRanks``, ``XmlDoc.cpp:41733``).
* word spam rank: 15 = no spam (weight (r+1)/16, ``Posdb.cpp``
  initWeights); a simple repetition heuristic lowers it.
* diversity rank: stored but weights are disabled at query time
  (``initWeights`` sets all 1.0), so we store MAXDIVERSITYRANK.
* a content-checksum term sharded by termid (``shardbytermid=1``) is
  emitted for duplicate detection (reference checksum terms,
  ``Posdb.h`` 'N' bit note).
"""

from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

from ..index import clusterdb, posdb, titledb
from ..index.collection import Collection
from ..utils import ghash
from ..utils.lang import detect_language
from ..utils.log import get_logger
from ..utils.membudget import g_membudget
from ..utils.url import normalize
from .tokenizer import (_WORD_RE, TokenizedDoc, tokenize_html,
                        tokenize_text)

log = get_logger("build")

CONTENT_HASH_PREFIX = "gbcontenthash"
SITE_PREFIX = "site"


@dataclass
class MetaList:
    """Everything one document contributes to the databases (the reference's
    serialized 'meta list', ``XmlDoc::getMetaList``)."""

    docid: int
    posdb_keys: np.ndarray
    titledb_key: np.ndarray
    title_rec: bytes
    clusterdb_key: np.ndarray
    links: list[tuple[str, str]]
    langid: int
    site: str
    words: list[str] | None = None  # doc vocabulary (feeds the Speller)
    #: linkees whose anchor set this add/remove touched — the next
    #: propagation wave (consumed by :func:`refresh_linkees`)
    refresh_targets: list = field(default_factory=list)
    #: resolved outlink edges [(linkee Url, anchor)] and the linkee →
    #: site-boundary map FROZEN at build time (stored in the TitleRec,
    #: so the delete path tombstones linkdb edges under the exact keys
    #: the add wrote, even if tagdb boundaries changed since)
    edges: list = field(default_factory=list)
    edge_sites: dict = field(default_factory=dict)
    #: this page's section content hashes (sectiondb records) and the
    #: subset demoted as boilerplate at build time — both stored in the
    #: TitleRec so tombstones regenerate the exact same postings even
    #: after the site's section votes move
    sections: list = field(default_factory=list)
    boiler_sections: list = field(default_factory=list)
    #: structured document fields (qajson-style): every extracted field
    #: (strings included — facet source) plus fielddb records for the
    #: numeric subset and the built-in ``date``
    fields: dict = field(default_factory=dict)
    fielddb_keys: np.ndarray | None = None
    fielddb_blobs: list = field(default_factory=list)


def doc_section_hashes(tdoc: TokenizedDoc) -> dict[int, int]:
    """section id → 32-bit content hash (Sections.cpp section content
    hashes): the repeatable-across-pages identity of each second-level
    container's word content."""
    from ..index.sectiondb import MIN_SECTION_WORDS
    nat = getattr(tdoc, "native", None)
    if nat is not None:
        return {int(p): ghash.hash64(c) & 0xFFFFFFFF
                for p, wc, c in zip(nat.sect_hash, nat.sect_words,
                                    nat.sect_content)
                if wc >= MIN_SECTION_WORDS}
    by_sid: dict[int, list[str]] = {}
    for sid, w in zip(tdoc.section_ids, tdoc.words):
        if sid:
            by_sid.setdefault(sid, []).append(w)
    return {sid: ghash.hash64(" ".join(ws)) & 0xFFFFFFFF
            for sid, ws in by_sid.items()
            if len(ws) >= MIN_SECTION_WORDS}


def _density_ranks(hashgroups: np.ndarray, sentences: np.ndarray) -> np.ndarray:
    """Vectorized getDensityRanks: per-sentence word counts for body/
    heading (and inlink text, where each anchor is its own sentence —
    the reference runs getDensityRanks over each link text string),
    whole-group counts for the rest."""
    n = len(hashgroups)
    out = np.empty(n, dtype=np.uint64)
    per_sentence = (hashgroups == posdb.HASHGROUP_BODY) | (
        hashgroups == posdb.HASHGROUP_HEADING) | (
        hashgroups == posdb.HASHGROUP_INLINKTEXT)
    if per_sentence.any():
        sent = sentences[per_sentence]
        uniq, inv, counts = np.unique(sent, return_inverse=True,
                                      return_counts=True)
        dr = posdb.MAXDENSITYRANK - (counts[inv] - 1)
        out[per_sentence] = np.clip(dr, 1, posdb.MAXDENSITYRANK)
    if (~per_sentence).any():
        hg = hashgroups[~per_sentence]
        uniq, inv, counts = np.unique(hg, return_inverse=True,
                                      return_counts=True)
        dr = posdb.MAXDENSITYRANK - (counts[inv] - 1)
        out[~per_sentence] = np.clip(dr, 1, posdb.MAXDENSITYRANK)
    return out


def _spam_ranks(words: list[str]) -> np.ndarray:
    """15 = clean. Words filling >12.5% of a ≥40-word doc get docked in
    proportion — a cheap stand-in for the reference's repetition-pattern
    detector (``Spam.cpp``-era logic folded into XmlDoc)."""
    n = len(words)
    ranks = np.full(n, posdb.MAXWORDSPAMRANK, dtype=np.uint64)
    if n < 40:
        return ranks
    uniq, inv, counts = np.unique(np.asarray(words, dtype=object),
                                  return_inverse=True,
                                  return_counts=True)
    frac = counts[inv] / n
    docked = np.maximum(
        2, (posdb.MAXWORDSPAMRANK * (1.0 - frac) * 0.8).astype(np.int64)
    ).astype(np.uint64)
    return np.where(frac > 0.125, docked, ranks)


def extract_fields(content: str) -> dict:
    """Structured document fields (the qajson/qaxml ingestion path,
    ``qa.cpp:2910``): a JSON document's scalars become fields (nested
    objects flatten with dots). Strings feed facets; numbers feed
    fielddb columns (gbmin/gbmax/gbsortby). HTML documents contribute
    only the built-in ``date`` field, taken from the page's date
    ``<meta>`` tags (``tdoc.meta_date``) in ``build_meta_list``."""
    import json as _json
    fields: dict = {}
    stripped = content.lstrip()
    if stripped.startswith("{"):
        try:
            obj = _json.loads(stripped)
        except ValueError:
            obj = None
        if isinstance(obj, dict):
            def flat(prefix, o):
                for k, v in o.items():
                    key = f"{prefix}{k}" if not prefix else \
                        f"{prefix}.{k}"
                    if isinstance(v, dict):
                        flat(key, v)
                    elif isinstance(v, (int, float, str)) \
                            and not isinstance(v, bool):
                        fields[key.lower()] = v
            flat("", obj)
    return fields


def _parse_date(val) -> float | None:
    """Best-effort document date → epoch seconds (meta tags carry
    ISO-8601 mostly)."""
    if isinstance(val, (int, float)):
        return float(val)
    if not isinstance(val, str) or not val:
        return None
    from datetime import datetime, timezone
    for fmt in ("%Y-%m-%dT%H:%M:%S", "%Y-%m-%d %H:%M:%S", "%Y-%m-%d",
                "%Y/%m/%d"):
        try:
            dt = datetime.strptime(val[:19], fmt)
            return dt.replace(tzinfo=timezone.utc).timestamp()
        except ValueError:
            continue
    return None


def _tokenize_doc(content: str, url: str, is_html: bool,
                  fields: dict | None = None) -> TokenizedDoc:
    """Structured (JSON) docs tokenize their string field values as the
    searchable text; everything else goes through the HTML/plain
    tokenizers. The gate and the text source are ALWAYS re-derived
    from the content itself: augmented fields (catdb categories, the
    built-in date — present in every stored titlerec) must never
    hijack tokenization, and add/tombstone must tokenize identically
    regardless of which fields dict the caller holds."""
    jf = extract_fields(content)
    if jf:
        text = " . ".join(str(v) for v in jf.values()
                          if isinstance(v, str))
        if text:
            return tokenize_text(text)
    return (tokenize_html(content, url) if is_html
            else tokenize_text(content))


def build_meta_list(
    url: str,
    content: str,
    *,
    is_html: bool = True,
    siterank: int = 0,
    langid: int | None = None,
    delete: bool = False,
    ts: float | None = None,
    inlinks: list | None = None,
    site: str | None = None,
    site_resolver=None,
    linkee_sites: dict | None = None,
    tdoc: TokenizedDoc | None = None,
    boiler_sections: list | None = None,
    sect_of: dict[int, int] | None = None,
    fields: dict | None = None,
) -> MetaList:
    """Compute every record one document contributes. ``delete=True``
    produces the same records as tombstones (reference: the old doc's
    meta list with negative keys, ``XmlDoc::getMetaList`` del path).

    ``inlinks`` is the harvested [(anchor text, linker siterank)] list
    (Msg25 LinkInfo): each anchor's words become HASHGROUP_INLINKTEXT
    postings with the linker's siterank in the wordspamrank slot
    (``XmlDoc::hashIncomingLinkText``; LINKER_WEIGHTS applies
    sqrt(1+siterank), ``Posdb.cpp:1136``). The snapshot is stored in the
    TitleRec so the delete path regenerates the exact same postings.

    ``site`` overrides the url-derived site boundary (SiteGetter/tagdb
    ``sitepathdepth`` — a subdirectory site on a hosting domain); it
    flows into the site: term, the clusterdb sitehash, and the stored
    TitleRec, so clustering and fielded search honor the boundary.
    ``site_resolver`` (normally ``Tagdb.site_of``) freezes each
    outlink's site boundary into the TitleRec; ``linkee_sites`` replays
    a stored map on the tombstone path so delete keys match add keys."""
    u = normalize(url)
    site = site or u.site
    docid = ghash.doc_id(u.full)
    if fields is None:
        fields = extract_fields(content)
    if tdoc is None:
        tdoc = _tokenize_doc(content, u.full, is_html, fields)
    edges = resolve_links(tdoc.links, u.full)
    if linkee_sites is None:
        resolver = site_resolver or (lambda lu: lu.site)
        linkee_sites = {lk.full: resolver(lk) for lk, _ in edges}
    if sect_of is None:
        sect_of = doc_section_hashes(tdoc)
    boiler = set(boiler_sections or [])

    nat = getattr(tdoc, "native", None)
    doc_words = list(tdoc.words)
    words = list(doc_words)

    if langid is None:
        langid = detect_language(doc_words, text=tdoc.text)

    # inlink anchor tokens: each anchor is its own sentence, in its own
    # position neighborhood (gaps > NONBODY_DIST_CAP=50 so words of
    # different anchors never look adjacent to pair scoring)
    inlinks = [(t, int(sr)) for t, sr in (inlinks or []) if t]
    il_words: list[str] = []
    il_wp: list[int] = []
    il_sent: list[int] = []
    il_spam: list[int] = []
    il_den: list[int] = []
    if inlinks:
        pos0 = (max(tdoc.wordpos) if tdoc.wordpos else 0) + 100
        sent0 = (max(tdoc.sentence_ids) if tdoc.sentence_ids else 0) + 1
        for j, (text, linker_sr) in enumerate(inlinks):
            aw = [w.lower() for w in _WORD_RE.findall(text)][:64]
            dr = int(np.clip(posdb.MAXDENSITYRANK - (len(aw) - 1), 1,
                             posdb.MAXDENSITYRANK))
            for i, w in enumerate(aw):
                il_words.append(w)
                il_wp.append(min(pos0 + i, posdb.MAXWORDPOS))
                il_sent.append(sent0 + j)
                il_spam.append(min(max(linker_sr, 0),
                                   posdb.MAXWORDSPAMRANK))
                il_den.append(dr)
            pos0 += len(aw) + 100
        words += il_words

    def _cat(a, b, dtype):
        ba = np.array(b, dtype=dtype)
        return np.concatenate([np.asarray(a, dtype=dtype), ba]) \
            if len(b) else np.asarray(a, dtype=dtype)

    if nat is not None:
        wordpos = _cat(nat.wordpos, il_wp, np.uint64)
        hashgroups = _cat(
            nat.hashgroup,
            [posdb.HASHGROUP_INLINKTEXT] * len(il_words), np.uint64)
        sentences = _cat(nat.sentence, il_sent, np.uint64)
    else:
        wordpos = _cat(tdoc.wordpos, il_wp, np.uint64)
        hashgroups = _cat(
            tdoc.hashgroups,
            [posdb.HASHGROUP_INLINKTEXT] * len(il_words), np.uint64)
        sentences = _cat(tdoc.sentence_ids, il_sent, np.uint64)

    delbit = 0 if delete else 1

    if len(words):
        if nat is not None:
            # native fast path: termids/density/spam precomputed in C++
            # for the doc+url tokens; the inlink block (Python-side
            # extras) computes per-anchor ranks by the same formulas
            termids = _cat(
                nat.termid,
                [ghash.term_id(w) for w in il_words], np.uint64)
            density = _cat(nat.density, il_den, np.uint64)
            doc_spam = nat.spam.astype(np.uint64)
        else:
            termids = np.array([ghash.term_id(w) for w in words],
                               dtype=np.uint64)
            density = _density_ranks(hashgroups, sentences)
            doc_spam = _spam_ranks(doc_words)
        if boiler:
            # boilerplate-section demotion (the Sections dup-vote →
            # score-weight flow): tokens of a section repeated across
            # the site get their spam rank docked
            from ..index.sectiondb import BOILER_SPAMRANK
            if nat is not None:
                bpaths = np.array(
                    [p for p, ch in sect_of.items() if ch in boiler],
                    dtype=np.uint64)
                bmask = np.isin(nat.sect, bpaths)
            else:
                bmask = np.array(
                    [sect_of.get(sid) in boiler
                     for sid in tdoc.section_ids], dtype=bool)
            doc_spam = np.where(bmask,
                                np.minimum(doc_spam, BOILER_SPAMRANK),
                                doc_spam)
        spam = _cat(doc_spam, il_spam, np.uint64)
        # bigrams: consecutive words within a sentence and hashgroup get a
        # combined term at the first word's position (reference Phrases.cpp;
        # bigram keys share the leading word's position — Posdb.cpp comment
        # "the wordpositions are exactly the same")
        bi = np.empty(0, np.int64)
        bids = np.empty(0, np.uint64)
        if nat is not None:
            # doc-token bigrams come precomputed; inlink bigrams (pairs
            # within one anchor) are appended with the same rule
            bi_parts = [nat.b_src.astype(np.int64)] \
                if len(nat.b_src) else []
            bid_parts = [nat.b_termid] if len(nat.b_termid) else []
            if len(il_words) > 1:
                n0 = len(nat.termid)
                ils = np.array(il_sent)
                pair = np.nonzero(ils[1:] == ils[:-1])[0]
                if len(pair):
                    bi_parts.append(pair + n0)
                    bid_parts.append(np.array(
                        [ghash.bigram_id(il_words[i], il_words[i + 1])
                         for i in pair], dtype=np.uint64))
            if bid_parts:
                bi = np.concatenate(bi_parts)
                bids = np.concatenate(bid_parts)
        elif len(words) > 1:
            same_sent = sentences[1:] == sentences[:-1]
            same_hg = hashgroups[1:] == hashgroups[:-1]
            # no phrases from positionless groups (url words, meta tags) —
            # their tokens aren't genuinely adjacent prose
            phrasable = (hashgroups[:-1] != posdb.HASHGROUP_INURL) & (
                hashgroups[:-1] != posdb.HASHGROUP_INMETATAG)
            bi = np.nonzero(same_sent & same_hg & phrasable)[0]
            if len(bi):
                bids = np.array(
                    [ghash.bigram_id(words[i], words[i + 1]) for i in bi],
                    dtype=np.uint64)
        # ONE pack per document: word keys + bigram keys + the site: and
        # checksum extra terms (reference hashUrl/checksum terms) — the
        # per-call broadcast overhead of separate packs measured as a
        # top indexing cost
        site_tid = ghash.term_id(site, prefix=SITE_PREFIX)
        content_hash = ghash.hash64(tdoc.text or content)
        chk_tid = ghash.term_id(f"{content_hash:x}",
                                prefix=CONTENT_HASH_PREFIX)
        two0 = np.zeros(2, np.uint64)
        n_all = len(termids) + len(bids) + 2
        sbt = np.zeros(n_all, np.uint64)
        sbt[-1] = 1  # checksum term shards by termid
        posdb_keys = posdb.pack(
            termid=np.concatenate(
                [termids, bids,
                 np.array([site_tid, chk_tid], np.uint64)]),
            docid=docid,
            wordpos=np.concatenate([wordpos, wordpos[bi], two0]),
            densityrank=np.concatenate([density, density[bi], two0]),
            wordspamrank=np.concatenate(
                [spam, spam[bi],
                 np.full(2, posdb.MAXWORDSPAMRANK, np.uint64)]),
            siterank=siterank,
            hashgroup=np.concatenate(
                [hashgroups, hashgroups[bi],
                 np.full(2, posdb.HASHGROUP_INURL, np.uint64)]),
            langid=langid, delbit=delbit, shardbytermid=sbt,
        )
    else:
        site_tid = ghash.term_id(site, prefix=SITE_PREFIX)
        content_hash = ghash.hash64(tdoc.text or content)
        posdb_keys = posdb.pack(
            termid=[site_tid,
                    ghash.term_id(f"{content_hash:x}",
                                  prefix=CONTENT_HASH_PREFIX)],
            docid=docid, wordpos=0, siterank=siterank, langid=langid,
            hashgroup=posdb.HASHGROUP_INURL, delbit=delbit,
            shardbytermid=[0, 1],
        )

    # structured fields: resolve the built-in date ONCE and store the
    # resolved dict in the titlerec, so the tombstone path regenerates
    # byte-identical fielddb records (same resolution the posdb
    # tombstones rely on)
    fields = dict(fields)
    dv = _parse_date(fields.get("date"))
    if dv is None:
        # HTML pages: the date <meta> tag (article:published_time etc.)
        dv = _parse_date(getattr(tdoc, "meta_date", "") or None)
    fields["date"] = dv if dv is not None else float(
        ts if ts is not None else time.time())
    from ..index import fielddb as fielddb_mod
    numeric = {f: v for f, v in fields.items()
               if isinstance(v, (int, float))
               and not isinstance(v, bool)}
    fdb_keys, fdb_blobs = fielddb_mod.make_records(
        docid, numeric, delbit=0 if delete else 1)

    if delete:
        title_rec = b""  # tombstone payload; skip the pointless compress
    else:
        # first heading run → the h1 title-fallback source (Title.cpp
        # falls back title → h1 → anchor → url; stored as lowercased
        # tokens — the tokenizer's columnar stream is the one source
        # both the python and native paths share). Vectorized: one
        # nonzero over the hashgroup column, not a per-token loop.
        h1 = ""
        hgarr = nat.hashgroup if nat is not None else \
            np.asarray(tdoc.hashgroups, dtype=np.uint64)
        hidx = np.nonzero(hgarr == posdb.HASHGROUP_HEADING)[0]
        if len(hidx):
            a = int(hidx[0])
            k = 0  # length of the contiguous first run, capped at 16
            while k < min(len(hidx), 16) and int(hidx[k]) == a + k:
                k += 1
            h1 = " ".join(tdoc.words[a:a + k])
        title_rec = titledb.make_title_rec(
            url=u.full, title=tdoc.title.strip(), text=tdoc.text,
            links=tdoc.links, site=site, langid=langid, siterank=siterank,
            content_hash=content_hash,
            ts=ts if ts is not None else time.time(),
            extra={"content": content, "is_html": is_html,
                   "h1": h1,
                   "meta_description": tdoc.meta_description,
                   "inlinks": [[t, sr] for t, sr in inlinks],
                   "linkee_sites": linkee_sites,
                   "sections": sorted(set(sect_of.values())),
                   "boiler_sections": sorted(boiler),
                   "fields": fields},
        )
    sitehash = ghash.hash64(site) & ((1 << clusterdb.SITEHASH_BITS) - 1)
    return MetaList(
        docid=docid,
        posdb_keys=posdb_keys,
        titledb_key=titledb.pack_key(docid, titledb.urlhash32(u.full), delbit),
        title_rec=title_rec,
        clusterdb_key=clusterdb.pack_key(docid, sitehash, langid, 0, delbit),
        links=tdoc.links,
        langid=langid,
        site=site,
        words=doc_words,
        edges=edges,
        edge_sites=linkee_sites,
        sections=sorted(set(sect_of.values())),
        boiler_sections=sorted(boiler),
        fields=fields,
        fielddb_keys=fdb_keys,
        fielddb_blobs=fdb_blobs,
    )


def absolutize(base: str, href: str) -> str | None:
    """Resolve an outlink href against its page URL (skip non-http)."""
    from urllib.parse import urldefrag, urljoin
    if href.startswith(("javascript:", "mailto:", "#")):
        return None
    absu = urldefrag(urljoin(base, href))[0] or None
    if absu and not absu.startswith(("http://", "https://")):
        return None
    return absu


def resolve_links(links: list[tuple[str, str]], linker_url: str):
    """Normalized (linkee, anchor) pairs for raw hrefs — the linkdb
    records the reference's meta list carries."""
    out = []
    for href, anchor in links:
        absu = absolutize(linker_url, href)
        if not absu:
            continue
        try:
            linkee = normalize(absu)
        except Exception:  # noqa: BLE001 — junk hrefs abound
            continue
        out.append((linkee, anchor))
    return out




def needs_link_refresh(fresh: list, stored: list) -> bool:
    """Should a linkee reindex to pick up its changed anchor set?
    Removals and changes always refresh (a stale weight-16 signal is
    worse than a missing one); growth refreshes exactly while small,
    then on doublings — the reference's deferred LinkInfo update
    interval, made deterministic, bounding hub-page reindexes to
    O(log inlinkers) during a crawl."""
    if sorted(fresh) == sorted(stored):
        return False
    if len(fresh) <= len(stored):
        return True
    if len(stored) < 8:
        return True
    return len(fresh) >= 2 * len(stored)


#: bound on anchor-refresh cascades along link chains (the reference
#: defers LinkInfo updates, so long chains settle over multiple crawl
#: rounds rather than in one synchronous walk)
MAX_REFRESH_DEPTH = 8


def refresh_linkees(linkees, own_site: str, *, get_doc, linkdb_of,
                    reindex, max_depth: int = MAX_REFRESH_DEPTH,
                    site_of=None) -> None:
    """Shared propagate step (single-node and sharded flows): for each
    external linkee already indexed, compare its stored inlink snapshot
    with a fresh harvest and reindex when stale.

    Propagation is an iterative breadth-first worklist with a visited
    set and depth cap — NOT recursion: ``reindex(linkee, rec)`` must
    perform a non-propagating reindex and return its ``MetaList`` (or
    None); the next wave is that list's ``refresh_targets``, enqueued
    here. Long link chains therefore cannot blow the Python stack, and
    a page is refreshed at most once per propagation."""
    from collections import deque

    site_of = site_of or (lambda u: u.site)
    seen: set[str] = set()
    work = deque((lk, own_site, 0) for lk in linkees)
    while work:
        linkee, src_site, depth = work.popleft()
        lk_site = site_of(linkee)
        if lk_site == src_site or linkee.full in seen:
            continue
        seen.add(linkee.full)
        rec = get_doc(linkee)
        if rec is None:
            continue
        fresh = linkdb_of(lk_site).inlinks_for_url(lk_site, linkee.full)
        stored = [tuple(x) for x in rec.get("inlinks") or []]
        if needs_link_refresh(fresh, stored):
            ml = reindex(linkee, rec)
            if ml is not None and depth + 1 < max_depth:
                work.extend((l2, linkee.site, depth + 1)
                            for l2 in ml.refresh_targets)


def index_document(coll: Collection, url: str, content: str, *,
                   is_html: bool = True, siterank: int = 0,
                   langid: int | None = None,
                   propagate: bool = True) -> MetaList:
    """Index (or re-index) one document into a collection — the
    ``XmlDoc::indexDoc`` flow: tombstone the old version if present,
    harvest this URL's inlink anchor text from linkdb (Msg25 LinkInfo),
    add the new records, record outlink edges, and re-index any already-
    indexed linkee whose anchor set changed — including linkees the OLD
    version linked to and the new one doesn't (their anchor goes away).

    Tagdb gates the whole flow (XmlDoc::indexDoc's EDOCBANNED path): a
    ``manualban`` on a containing site drops any indexed version and
    returns None; ``sitepathdepth`` widens the site boundary;
    ``siterank`` pins site quality over the link-derived rank."""
    u = normalize(url)
    banned, site, sr_override = coll.tagdb.index_gate(u)
    if banned:
        remove_document(coll, url, propagate=propagate)
        log.info("tagdb manualban: %s not indexed", url)
        return None
    if sr_override is not None:
        siterank = sr_override
    old = remove_document(coll, url, _count=False, propagate=False)
    inlinks = coll.linkdb.inlinks_for_url(site, u.full)
    # boilerplate gate (Sections dup votes): sections this page shares
    # with enough sibling pages of the site demote at build time
    flds = extract_fields(content)
    # directory category tree (Catdb): a filed site's docs carry catid/
    # category fields — gbmin:catid:/gbfacet:category do the rest
    flds.update(coll.catdb.doc_fields(site))
    tdoc = _tokenize_doc(content, u.full, is_html, flds)
    sect_of = doc_section_hashes(tdoc)
    boiler = coll.sectiondb.boiler_set(site, sect_of.values())
    ml = build_meta_list(url, content, is_html=is_html, siterank=siterank,
                         langid=langid, inlinks=inlinks, site=site,
                         site_resolver=coll.tagdb.site_of, tdoc=tdoc,
                         boiler_sections=boiler, sect_of=sect_of,
                         fields=flds)
    coll.posdb.add(ml.posdb_keys)
    coll.titledb.add(ml.titledb_key.reshape(1), [ml.title_rec])
    coll.clusterdb.add(ml.clusterdb_key.reshape(1))
    if ml.fielddb_keys is not None and len(ml.fielddb_keys):
        coll.fielddb.add(ml.fielddb_keys, ml.fielddb_blobs)
    coll.sectiondb.add_page_sections(site, u.full, ml.sections)
    coll.titlerec_cache.pop(ml.docid, None)
    if ml.words:
        coll.speller.add_doc_words(ml.words)
    if old is None:
        coll.doc_added()
    # record outlink edges with anchor text (this page's siterank is the
    # linker rank riding each edge), then refresh affected linkees:
    # the new edge set plus any former linkees whose edge was tombstoned
    edges = ml.edges
    for linkee, anchor in edges:
        coll.linkdb.add_link(
            ml.edge_sites.get(linkee.full, linkee.site), site, u.full,
            linkee_url=linkee.full, anchor_text=anchor,
            linker_siterank=siterank)
    ml.refresh_targets = [e[0] for e in edges]
    if old is not None:
        ml.refresh_targets += old.refresh_targets
    if propagate:
        refresh_linkees(
            ml.refresh_targets, site,
            get_doc=lambda lk: get_document(coll, url=lk.full),
            linkdb_of=lambda _site: coll.linkdb,
            reindex=lambda lk, rec: reindex_document(
                coll, lk.full, propagate=False),
            site_of=coll.tagdb.site_of)
    log.debug("indexed %s docid=%d keys=%d inlinks=%d", url, ml.docid,
              len(ml.posdb_keys), len(inlinks))
    return ml


def index_batch(coll: Collection, docs, *, is_html: bool = True,
                siterank: int = 0, langid: int | None = None,
                propagate: bool = True) -> list[MetaList | None]:
    """Bulk indexing: N documents in one pass — the TPU-era shape of
    the reference's fully-async build pipeline (SURVEY §2.5). Same
    records as N ``index_document`` calls, restructured into three
    phases so per-document overheads amortize:

    * **reads first** (tagdb gates, existing-doc probes, inlink
      harvests, boilerplate votes) — no writes interleave, so the
      memtables seal ONCE per batch instead of once per document
      (the seal-thrash was a top indexing cost);
    * **compute** (tokenize + meta lists) — pure, per document;
    * **writes last**, one batched Rdb add per database: a single
      concatenated posdb add, one titledb/clusterdb add, then linkdb
      edges and section votes.

    Documents already in the index (re-adds) and within-batch duplicate
    URLs fall back to the sequential path — bulk loads are
    overwhelmingly fresh URLs. Returns one MetaList (or None for
    banned/failed docs) per input, in order."""
    out: list[MetaList | None] = [None] * len(docs)
    seen_urls: dict[str, int] = {}
    leftovers: list[tuple[int, str, str]] = []  # dups/re-adds, last
    work = []  # (i, u, url, content, site, siterank)
    for i, (url, content) in enumerate(docs):
        try:
            u = normalize(url)
        except Exception:  # noqa: BLE001 — junk URLs abound in bulk
            continue
        banned, site, sr_override = coll.tagdb.index_gate(u)
        if banned:
            remove_document(coll, url, propagate=propagate)
            log.info("tagdb manualban: %s not indexed", url)
            continue
        if u.full in seen_urls or get_document(coll, url=u.full) \
                is not None:
            # duplicate within batch or re-add → sequential fallback,
            # DEFERRED until after the batch's records are written:
            # indexing it now would race phase C (the first occurrence
            # isn't in the Rdb yet, so newest-wins would resurrect it
            # and doc accounting would double-count)
            leftovers.append((i, url, content))
            continue
        seen_urls[u.full] = i
        work.append((i, u, url, content, site,
                     siterank if sr_override is None else sr_override))

    # --- phase A reads: inlink harvests + boilerplate votes ---
    reads = []
    for i, u, url, content, site, sr in work:
        inlinks = coll.linkdb.inlinks_for_url(site, u.full)
        flds = extract_fields(content)
        flds.update(coll.catdb.doc_fields(site))
        tdoc = _tokenize_doc(content, u.full, is_html, flds)
        sect_of = doc_section_hashes(tdoc)
        boiler = coll.sectiondb.boiler_set(site, sect_of.values())
        reads.append((inlinks, tdoc, boiler, sect_of, flds))

    # --- phase B compute: meta lists (pure) ---
    metas = []
    for (i, u, url, content, site, sr), \
            (inlinks, tdoc, boiler, sect_of, flds) in zip(work, reads):
        ml = build_meta_list(url, content, is_html=is_html,
                             siterank=sr, langid=langid,
                             inlinks=inlinks, site=site,
                             site_resolver=coll.tagdb.site_of,
                             tdoc=tdoc, boiler_sections=boiler,
                             sect_of=sect_of, fields=flds)
        metas.append(ml)
        out[i] = ml

    def _run_leftovers():
        for i, url, content in leftovers:
            out[i] = index_document(coll, url, content,
                                    is_html=is_html,
                                    siterank=siterank, langid=langid,
                                    propagate=propagate)

    if not metas:
        _run_leftovers()
        return out
    # --- phase C writes: ONE add per Rdb, gated by the memory budget.
    # Over budget the batch SHEDS: split in half and write the halves
    # separately, so the concatenated key images stay bounded and the
    # memtable can dump between chunks (the g_mem degradation arm for
    # the build pipeline — slower, never OOM).
    def _phase_c_estimate(chunk):
        return (sum(int(ml.posdb_keys.nbytes) for ml in chunk)
                + sum(len(ml.title_rec) for ml in chunk)
                + 64 * len(chunk))  # small keys (title/cluster/field)

    def _phase_c_write(chunk):
        coll.posdb.add(np.concatenate([ml.posdb_keys for ml in chunk]))
        coll.titledb.add(
            np.concatenate([ml.titledb_key.reshape(1) for ml in chunk]),
            [ml.title_rec for ml in chunk])
        coll.clusterdb.add(
            np.concatenate([ml.clusterdb_key.reshape(1) for ml in chunk]))
        withf = [ml for ml in chunk
                 if ml.fielddb_keys is not None and len(ml.fielddb_keys)]
        if withf:
            coll.fielddb.add(
                np.concatenate([ml.fielddb_keys for ml in withf]),
                [b for ml in withf for b in ml.fielddb_blobs])

    pending = [metas]
    while pending:
        chunk = pending.pop(0)
        with g_membudget.reserving(
                "docproc", _phase_c_estimate(chunk)) as granted:
            if not granted and len(chunk) > 1:
                mid = len(chunk) // 2
                log.warning("index_batch: %d-doc write over memory "
                            "budget — shedding to halves", len(chunk))
                pending[:0] = [chunk[:mid], chunk[mid:]]
                continue
            # a refused SINGLE doc still writes: correctness beats the
            # budget once degradation has nothing left to shed
            _phase_c_write(chunk)
    for (i, u, url, content, site, sr), ml in zip(work, metas):
        coll.sectiondb.add_page_sections(site, u.full, ml.sections)
        coll.titlerec_cache.pop(ml.docid, None)
        if ml.words:
            coll.speller.add_doc_words(ml.words)
        coll.doc_added()
        for linkee, anchor in ml.edges:
            coll.linkdb.add_link(
                ml.edge_sites.get(linkee.full, linkee.site), site,
                u.full, linkee_url=linkee.full, anchor_text=anchor,
                linker_siterank=sr)
        ml.refresh_targets = [e[0] for e in ml.edges]
    if propagate:
        for (i, u, url, content, site, sr), ml in zip(work, metas):
            if ml.refresh_targets:
                refresh_linkees(
                    ml.refresh_targets, site,
                    get_doc=lambda lk: get_document(coll, url=lk.full),
                    linkdb_of=lambda _site: coll.linkdb,
                    reindex=lambda lk, rec: reindex_document(
                        coll, lk.full, propagate=False),
                    site_of=coll.tagdb.site_of)
    _run_leftovers()
    return out


def reindex_document(coll: Collection, url: str, *,
                     propagate: bool = True) -> MetaList | None:
    """Re-index a document from its stored content — fresh inlink
    harvest + recomputed link-derived siterank (the reference's reindex
    path, ``Repair.cpp``/``PageReindex`` semantics)."""
    from ..spider.linkdb import site_rank
    rec = get_document(coll, url=url)
    if rec is None:
        return None
    u = normalize(url)
    return index_document(
        coll, url, rec.get("content", rec["text"]),
        is_html=rec.get("is_html", True),
        siterank=site_rank(
            coll.linkdb.site_num_inlinks(coll.tagdb.site_of(u))),
        langid=rec.get("langid"), propagate=propagate)


def tombstone_meta_list(rec: dict) -> MetaList:
    """Regenerate a stored document's records as tombstones (the
    reference's delete/reindex path rebuilds the OLD doc's meta list with
    negative keys, ``XmlDoc::getMetaList`` del path). Shared by the
    single-shard and sharded delete flows so the regeneration contract
    lives in one place."""
    return build_meta_list(rec["url"], rec.get("content", rec["text"]),
                           is_html=rec.get("is_html", True),
                           siterank=rec.get("siterank", 0),
                           langid=rec.get("langid"), delete=True,
                           ts=rec.get("ts"),
                           inlinks=[tuple(x) for x in
                                    rec.get("inlinks") or []],
                           site=rec.get("site"),
                           linkee_sites=rec.get("linkee_sites"),
                           boiler_sections=rec.get("boiler_sections"),
                           fields=rec.get("fields"))


def remove_document(coll: Collection, url: str, _count: bool = True,
                    propagate: bool = True) -> MetaList | None:
    """Delete a document: regenerate its records from the stored TitleRec
    content and add them as tombstones (the reference's reindex/del path
    regenerates the old meta list the same way). Returns the tombstone
    meta list (truthy) so re-index callers can diff old/new edge sets."""
    u = normalize(url)
    docid = ghash.doc_id(u.full)
    existing = coll.titledb.get_list(titledb.start_key(docid),
                                     titledb.end_key(docid))
    # discriminate 38-bit docid collisions by the urlhash packed in the key
    # (reference: probable-docid collision handling in Titledb/XmlDoc)
    want = titledb.urlhash32(u.full)
    match = np.nonzero(
        titledb.unpack_key(existing.keys)["urlhash32"] == np.uint64(want)
    )[0] if len(existing) else np.empty(0, dtype=np.int64)
    if not len(match):
        return None
    rec = titledb.read_title_rec(existing.payload(int(match[-1])))
    ml = tombstone_meta_list(rec)
    coll.posdb.add(ml.posdb_keys)
    coll.titledb.add(ml.titledb_key.reshape(1), [b""])
    coll.clusterdb.add(ml.clusterdb_key.reshape(1))
    if ml.fielddb_keys is not None and len(ml.fielddb_keys):
        coll.fielddb.add(ml.fielddb_keys, ml.fielddb_blobs)
    coll.sectiondb.remove_page_sections(
        ml.site, u.full, rec.get("sections") or [])
    coll.titlerec_cache.pop(ml.docid, None)
    # tombstone this page's outlink edges so its anchors stop feeding
    # linkee rankings (the old meta list's linkdb records, negated)
    from ..spider.linkdb import pack_key as link_key
    edges = ml.edges
    for linkee, _anchor in edges:
        # delete under the boundary FROZEN at add time (stored in the
        # titlerec); legacy recs without the map fall back to tagdb
        lk_site = ml.edge_sites.get(linkee.full) \
            or coll.tagdb.site_of(linkee)
        if lk_site == ml.site:
            continue
        coll.linkdb.rdb.delete(
            link_key(lk_site, linkee.full, ml.site, u.full).reshape(1))
    if ml.words:
        coll.speller.remove_doc_words(ml.words)
    if _count:
        coll.doc_removed()
    ml.refresh_targets = [e[0] for e in edges]
    if propagate:
        # former linkees lose this page's anchor — refresh them
        refresh_linkees(
            ml.refresh_targets, ml.site,
            get_doc=lambda lk: get_document(coll, url=lk.full),
            linkdb_of=lambda _site: coll.linkdb,
            reindex=lambda lk, _rec: reindex_document(
                coll, lk.full, propagate=False),
            site_of=coll.tagdb.site_of)
    return ml


def get_document(coll: Collection, url: str | None = None,
                 docid: int | None = None) -> dict | None:
    """TitleRec lookup by url or docid (reference Msg22 titlerec fetch +
    PageGet cached-page view), behind the collection's RdbCache-style
    parsed-rec cache."""
    want = None
    if docid is None:
        assert url is not None
        full = normalize(url).full
        docid = ghash.doc_id(full)
        want = titledb.urlhash32(full)
    elif docid in coll.titlerec_cache:
        return coll.titlerec_cache[docid]
    lst = coll.titledb.get_list(titledb.start_key(docid),
                                titledb.end_key(docid))
    rec = None
    if len(lst):
        idx = len(lst) - 1
        if want is not None:  # docid-collision discrimination
            match = np.nonzero(
                titledb.unpack_key(lst.keys)["urlhash32"]
                == np.uint64(want))[0]
            idx = int(match[-1]) if len(match) else -1
        if idx >= 0:
            payload = lst.payload(idx)
            rec = titledb.read_title_rec(payload) if payload else None
    if want is None:  # only docid-keyed lookups are cacheable
        if len(coll.titlerec_cache) >= coll.titlerec_cache_max:
            coll.titlerec_cache.clear()
        coll.titlerec_cache[docid] = rec
    return rec
