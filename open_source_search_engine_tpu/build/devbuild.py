"""Device ingest plane — the posting sort/dedup/pack pipeline on-chip.

Reference seam (SURVEY §7 hard part (d)): ``RdbDump`` writes sorted
runs, ``RdbMerge``/``Msg5`` N-way-merges them with newest-wins dedup
and +/- annihilation, and ``Msg4``/``addsinprogress.bin`` folds fresh
adds in behind serving. Here those stages are jitted sort/scan
programs over the 18-byte posdb keys, so a full base build is one
device program instead of ~450 s of host NumPy (BENCH_r04):

1. **merge**: the runs' key columns are concatenated host-side (no
   host sort — enforced by the ``host-sort`` osselint rule), split
   into uint32 words, and sorted on-device by (key-sans-delbit asc,
   recency desc) — a stable lexsort (``_lexsort``: one single-key pass
   per key word), so ties resolve exactly like
   ``rdblite._dedup_newest``. First-of-group survives; surviving
   tombstones annihilate; survivors compact to the front with a
   stable flag sort.
2. **docidx**: distinct docids rank by a second on-device sort (the
   ``np.unique``/``searchsorted`` collapse).
3. **derive**: occurrence ranks (cummax scan), the ``occ < P`` store
   cap, run starts, per-(term,doc) impact bounds, packed payload and
   docc columns, and the term directory — all segmented scans and
   scatters over bucketed static shapes (jitwatch-clean: repeated
   same-bucket batches reuse one trace).

Bit-exactness contract: every output column is bitwise identical to
the host pipeline in ``query/devindex.py`` (``_build_base`` /
``_build_delta``), which stays as the parity oracle and the fallback
path. The float-sensitive part is the impact sum: NumPy's
``add.reduceat`` folds each (term, doc) pair's candidate scores
left-to-right, so the kernel scatters each pair's contributions into
per-position slots and folds them with :data:`MAX_POSITIONS` explicit
adds in the same order (x + 0.0 is exact for x ≥ +0.0, so interleaved
zero contributions don't perturb the sum). Candidate ranking reuses
the monotone bitcast trick: for non-negative f32, descending value
order equals ascending ``~bitcast_u32`` order, dodging any -0.0
float-comparator divergence between XLA and NumPy sorts.

uint64 never touches the device: the 18-byte key splits into five
uint32 words (n0 | n1 lo/hi | n2 lo/hi) and docids ride as 32+6 bit
pairs, so the kernels run identically with and without jax x64.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..index import posdb
from ..utils import devwatch, jitwatch
from ..utils.log import get_logger
from ..utils.stats import g_stats
from ..query import weights
from ..query.packer import IMPACT_SCALE, MAX_POSITIONS, _bucket

log = get_logger("devbuild")

# the ingest plane is a jit entry point of its own (bench BENCH_BUILD
# imports it before any query module) — same opt-in as devindex
jitwatch.maybe_enable()
devwatch.maybe_enable()

#: column bucket quantum — mirrors devindex.COL_QUANTUM (kept numeric
#: here: devindex imports this module, not the other way round)
COL_QUANTUM = 1 << 15

P = MAX_POSITIONS

_U32 = jnp.uint32


def enabled() -> bool:
    """OSSE_DEVBUILD gates the device ingest plane (default ON); the
    host NumPy pipeline stays available as oracle and fallback."""
    return os.environ.get("OSSE_DEVBUILD", "1") != "0"


# ---------------------------------------------------------------------------
# small shared scan/segment helpers (traced inside the programs)
# ---------------------------------------------------------------------------


def _neq_prev(*cols):
    """Boolean "differs from previous row" over parallel columns; row 0
    is always True (the host pipelines' ``np.ones`` + shifted
    compare)."""
    n = cols[0].shape[0]
    diff = jnp.zeros(n - 1, bool)
    for c in cols:
        diff = diff | (c[1:] != c[:-1])
    return jnp.concatenate([jnp.ones((1,), bool), diff])


def _compact(order, *cols):
    return tuple(c[order] for c in cols)


def _lexsort(keys):
    """``jnp.lexsort(keys)`` (last key primary, stable) as one stable
    single-key sort per key, least significant first — the same
    permutation by construction. The TPU compiler's time for ONE sort
    grows about quadratically with its operand count: the base
    program's 7-key merge sort alone compiled 428 s for a v5e at
    N = 2^25, these seven 2-operand passes compile in 41 s (CHANGES.md,
    PR 22), and a cold node pays that before its first answer."""
    order = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    for k in keys:
        _, order = lax.sort((k[order], order), num_keys=1,
                            is_stable=True)
    return order


def _count_true(m):
    return jnp.sum(m, dtype=jnp.int32)


def _seg_pos(start_flags, idx):
    """Position of each row within its segment (segments marked by
    ``start_flags``) — the running-max scan both host ``_occ_ranks``
    and the impact ranker use."""
    return idx - lax.cummax(jnp.where(start_flags, idx, 0))


# ---------------------------------------------------------------------------
# field math (bit-identical ports of posdb.unpack / pack_payload /
# _posscore_np / demote_impacts)
# ---------------------------------------------------------------------------


def _posscore(hg, den, spam):
    """BASE·posw² per posting — same table gathers and multiply
    association as ``devindex._posscore_np`` (f32 throughout)."""
    hgw = jnp.asarray(weights.HASH_GROUP_WEIGHTS)[hg]
    denw = jnp.asarray(weights.DENSITY_WEIGHTS)[den]
    is_il = hg == posdb.HASHGROUP_INLINKTEXT
    spamw = jnp.where(is_il,
                      jnp.asarray(weights.LINKER_WEIGHTS)[spam],
                      jnp.asarray(weights.WORD_SPAM_WEIGHTS)[spam])
    posw = hgw * denw * spamw
    return jnp.float32(weights.BASE_SCORE) * posw * posw, is_il


def _demote(a):
    """``packer.demote_impacts`` on device: f32 → f16 at 1/IMPACT_SCALE
    rounded UP (nextafter == bits+1 for positive finite f16, including
    the 0 → smallest-subnormal step)."""
    s = a * jnp.float32(1.0 / IMPACT_SCALE)
    h = s.astype(jnp.float16)
    low = h.astype(jnp.float32) < s
    bits = lax.bitcast_convert_type(h, jnp.uint16) + jnp.uint16(1)
    h = jnp.where(low, lax.bitcast_convert_type(bits, jnp.float16), h)
    return jnp.maximum(h, jnp.float16(
        np.finfo(np.float16).smallest_subnormal))


# ---------------------------------------------------------------------------
# the shared derive stage: sorted (term, doc) rows → base/delta columns
# ---------------------------------------------------------------------------


def _derive(tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg, n):
    """Everything downstream of the sort, shared by base and delta:
    occurrence ranks, the store cap, run boundaries, the term
    directory, packed payload/docc and the exact impact bounds.

    Inputs are padded to the working bucket; ``n`` (traced scalar)
    marks the valid prefix. Rows must already be sorted by
    (termid, docidx[, wordpos]) — both callers' sorts guarantee it.
    Output columns are zero beyond their own counters (matching the
    host ``_pad_col`` convention), so callers can slice/pad them
    straight into device column buffers."""
    N = tid_lo.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    valid = idx < n

    with jax.named_scope("build.term_directory"):
        # --- pre-cap boundaries: term change + (term, doc) pair change ---
        tch0 = _neq_prev(tid_lo, tid_hi) & valid
        np0 = (_neq_prev(tid_lo, tid_hi) | _neq_prev(docidx)) & valid
        occ = _seg_pos(np0, idx)

        # df BEFORE the store cap (the Msg36 termfreq precompute): distinct
        # (term, doc) pairs per term — integer scatter-add, deterministic
        trank0 = jnp.cumsum(tch0.astype(jnp.int32)) - 1
        n_terms = _count_true(tch0)
        df = jnp.zeros(N, jnp.int32).at[
            jnp.where(valid, trank0, N)].add(np0.astype(jnp.int32),
                                             mode="drop")
        d_tid_lo = jnp.zeros(N, _U32).at[
            jnp.where(tch0, trank0, N)].set(tid_lo, mode="drop")
        d_tid_hi = jnp.zeros(N, _U32).at[
            jnp.where(tch0, trank0, N)].set(tid_hi, mode="drop")

    with jax.named_scope("build.store_cap"):
        # --- store cap: scoring consumes ≤ P positions per pair ---
        keep = (occ < P) & valid
        oc = _lexsort(((~keep).astype(_U32),))
        (tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg,
         occ) = _compact(oc, tid_lo, tid_hi, docidx, hg, den, spam, wp,
                         sr, lg, occ)
        nk = _count_true(keep)
        valid = idx < nk

        payload = jnp.where(
            valid,
            wp | (hg << 18) | (den << 22) | (spam << 27), _U32(0))
        docc = jnp.where(
            valid, (docidx.astype(_U32) << 4) | occ.astype(_U32), _U32(0))

    with jax.named_scope("build.pair_runs"):
        # --- doc-level runs: one entry per (term, doc) pair ---
        newpair = (_neq_prev(tid_lo, tid_hi) | _neq_prev(docidx)) & valid
        pair_id = jnp.cumsum(newpair.astype(jnp.int32)) - 1
        n_pairs = _count_true(newpair)
        pair_tgt = jnp.where(newpair, pair_id, N)
        runstart = jnp.zeros(N, jnp.int32).at[pair_tgt].set(idx, mode="drop")
        doc_col = jnp.zeros(N, jnp.int32).at[pair_tgt].set(
            docidx, mode="drop")
        count = jnp.zeros(N, jnp.int32).at[
            jnp.where(valid, pair_id, N)].add(1, mode="drop")
        cnt_col = jnp.minimum(count, P).astype(jnp.uint8)

        tch = _neq_prev(tid_lo, tid_hi) & valid
        trank = jnp.cumsum(tch.astype(jnp.int32)) - 1
        term_tgt = jnp.where(tch, trank, N)
        # pair index at a term start == searchsorted(runstart, tstart)
        dir_dstart = jnp.zeros(N, jnp.int32).at[term_tgt].set(
            pair_id, mode="drop")
        dir_pstart = jnp.zeros(N, jnp.int32).at[term_tgt].set(
            idx, mode="drop")

    with jax.named_scope("build.impacts"):
        # --- exact impacts (the _impacts_np candidate-rank-sum, on-chip) --
        ps, il = _posscore(hg.astype(jnp.int32), den.astype(jnp.int32),
                           spam.astype(jnp.int32))
        mhg = jnp.asarray(weights.MAPPED_HASHGROUP)[hg.astype(jnp.int32)]
        pid_key = jnp.where(valid, pair_id, jnp.int32(N))
        o = _lexsort((mhg, pid_key))
        ps_o, il_o, mh_o, pid_o, valid_o = _compact(
            o, ps, il, mhg, pid_key, valid)
        gch = (_neq_prev(pid_o) | _neq_prev(mh_o)) & valid_o
        gid = jnp.cumsum(gch.astype(jnp.int32)) - 1
        gmax = jnp.zeros(N, jnp.float32).at[
            jnp.where(valid_o, gid, N)].max(ps_o, mode="drop")
        cand = (il_o | gch) & valid_o
        cval = jnp.where(il_o, ps_o, gmax[jnp.where(valid_o, gid, 0)])
        pch = _neq_prev(pid_o) & valid_o
        # rank candidates within each pair, descending cval: stable sort by
        # (pair, non-candidate-last, ~bitcast(cval)) — monotone for f32 ≥ 0
        ckey = ~lax.bitcast_convert_type(cval, _U32)
        o3 = _lexsort((ckey, (~cand).astype(_U32), pid_o))
        seg = _neq_prev(pid_o[o3])
        rank = jnp.zeros(N, jnp.int32).at[o3].set(_seg_pos(seg, idx))
        contrib = jnp.where(cand & (rank < weights.MAX_TOP), cval,
                            jnp.float32(0.0))
        # pair sums folded LEFT-TO-RIGHT like np.add.reduceat: position-q
        # rows scatter to unique pair slots, then P sequential adds
        q = _seg_pos(pch, idx)
        acc = jnp.zeros(N, jnp.float32)
        for j in range(P):
            sel = (q == j) & valid_o
            acc = acc + jnp.zeros(N, jnp.float32).at[
                jnp.where(sel, pid_o, N)].set(contrib, mode="drop")
        pvalid = idx < n_pairs
        imp32 = jnp.where(pvalid, jnp.maximum(acc, jnp.float32(1e-30)),
                          jnp.float32(0.0))
        imp16 = jnp.where(pvalid, _demote(imp32), jnp.float16(0.0))

    return dict(
        payload=payload, docc=docc, pocc=jnp.where(
            valid, occ, jnp.uint32(0)).astype(jnp.uint8),
        docidx=jnp.where(valid, docidx, 0),
        siterank=jnp.where(valid, sr, _U32(0)).astype(jnp.uint8),
        langid=jnp.where(valid, lg, _U32(0)).astype(jnp.uint8),
        doc_col=doc_col, imp32=imp32, imp16=imp16,
        rs=jnp.where(pvalid, runstart, 0),
        cnt=jnp.where(pvalid, cnt_col, jnp.uint8(0)),
        dir_tid_lo=d_tid_lo, dir_tid_hi=d_tid_hi, df=df,
        dir_dstart=dir_dstart, dir_pstart=dir_pstart,
        counters=jnp.stack([nk, n_pairs, n_terms]))


# ---------------------------------------------------------------------------
# base program: N-way run merge + annihilation + docidx + derive
# ---------------------------------------------------------------------------


@jax.jit
def _base_program(n0, n1lo, n1hi, n2lo, n2hi, rec, n):
    """Full base build from concatenated run key words. One traced
    program per input bucket; ``n``/``rec`` ride as traced operands so
    corpus size changes inside a bucket never retrace."""
    N = n0.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    valid = idx < n

    with jax.named_scope("build.merge_runs"):
        # --- RdbMerge/Msg5: newest-wins dedup + tombstone annihilation ---
        n0c = n0 & ~_U32(1)
        negrec = _U32(0x7FFFFFFF) - rec
        order = _lexsort((negrec, n0c, n1lo, n1hi, n2lo, n2hi,
                          (~valid).astype(_U32)))
        n0_s, n0c_s, l1, h1, l2, h2, valid_s = _compact(
            order, n0, n0c, n1lo, n1hi, n2lo, n2hi, valid)
        first = _neq_prev(n0c_s, l1, h1, l2, h2)
        keep = first & (n0_s & _U32(1)).astype(bool) & valid_s
        oc = _lexsort(((~keep).astype(_U32),))
        n0_s, l1, h1, l2, h2 = _compact(oc, n0_s, l1, h1, l2, h2)
        n_merged = _count_true(keep)
        valid = idx < n_merged

    with jax.named_scope("build.unpack_keys"):
        # --- posdb.unpack, bit-split (no uint64 on device) ---
        tid_lo = (h2 << 16) | (l2 >> 16)
        tid_hi = h2 >> 16
        d_lo = ((l2 & _U32(0x3FF)) << 22) | (h1 >> 10)   # docid bits 0..31
        d_hi = (l2 >> 10) & _U32(0x3F)                   # docid bits 32..37
        sr = (h1 >> 5) & _U32(0xF)
        lg = (h1 & _U32(0x1F)) | (((n0_s >> 3) & _U32(1)) << 5)
        wp = l1 >> 14
        hg = (l1 >> 10) & _U32(0xF)
        spam = (l1 >> 6) & _U32(0xF)
        den = (n0_s >> 11) & _U32(0x1F)

    with jax.named_scope("build.docidx"):
        # --- docidx: rank of each distinct docid (np.unique collapse) ---
        od = _lexsort((d_lo, d_hi, (~valid).astype(_U32)))
        dl_s, dh_s, v_s = _compact(od, d_lo, d_hi, valid)
        newdoc = _neq_prev(dl_s, dh_s) & v_s
        docrank = jnp.cumsum(newdoc.astype(jnp.int32)) - 1
        n_docs = _count_true(newdoc)
        docidx = jnp.zeros(N, jnp.int32).at[od].set(docrank)
        docidx = jnp.where(valid, docidx, 0)
        doc_tgt = jnp.where(newdoc, docrank, N)
        bd_lo = jnp.zeros(N, _U32).at[doc_tgt].set(dl_s, mode="drop")
        bd_hi = jnp.zeros(N, _U32).at[doc_tgt].set(dh_s, mode="drop")

    out = _derive(tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg,
                  n_merged)
    out.update(bd_lo=bd_lo, bd_hi=bd_hi,
               base_counters=jnp.stack([n_merged, n_docs]))
    return out


# ---------------------------------------------------------------------------
# delta program: sort the memtable positives, then the shared derive
# ---------------------------------------------------------------------------


@jax.jit
def _delta_program(tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg, m):
    """Delta fold: the memtable positives sorted by (termid, docidx,
    wordpos) — new docs' indexes aren't docid-monotonic, same key as
    the host path — then the shared derive stage."""
    N = tid_lo.shape[0]
    valid = jnp.arange(N, dtype=jnp.int32) < m
    with jax.named_scope("build.sort_memtable"):
        o = _lexsort((wp, docidx, tid_lo, tid_hi,
                      (~valid).astype(_U32)))
        tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg = _compact(
            o, tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg)
    return _derive(tid_lo, tid_hi, docidx, hg, den, spam, wp, sr, lg, m)


# ---------------------------------------------------------------------------
# doc-meta and cube-row kernels (shared by base + delta paths)
# ---------------------------------------------------------------------------


@jax.jit
def _doc_meta(sr_tab, dl_tab, docidx, sr_rows, lg_rows, n):
    """First-posting-per-doc siterank/langid (the reference
    getSiteRank(miniMergedList[0]) role): segment-min picks each doc's
    first capped row; docs with no rows keep their table entry."""
    N = docidx.shape[0]
    D = sr_tab.shape[0]
    idx = jnp.arange(N, dtype=jnp.int32)
    valid = idx < n
    firstrow = jnp.full(D, N, jnp.int32).at[
        jnp.where(valid, docidx, D)].min(idx, mode="drop")
    has = firstrow < N
    g = jnp.clip(firstrow, 0, N - 1)
    return (jnp.where(has, sr_rows[g], sr_tab),
            jnp.where(has, lg_rows[g], dl_tab))


@partial(jax.jit, static_argnames=("D", "n_positions", "total",
                                   "n_lanes"))
def _cube_rows(payload, docc, starts, cum, D: int, n_positions: int,
               total: int, n_lanes: int):
    """Materialized cube rows by one flattened scatter, handed back in
    the ONE form the resident cube has: quarter rows ``[Vc·4, P/4, D]``
    (row ``s·4 + q`` holds occurrences q·P/4 .. (q+1)·P/4 - 1 of slot
    s's term, for every doc) — the array the FD kernel DMAs from as it
    stands. The flat → quarter-row relayout (a copy of the whole cube
    on a TPU) is paid here, once a base build, so that no wave program
    pays it. The scatter destination is derived from the resident docc
    column (docidx<<4 | occ), so the host ships only the per-slot
    (start, cumlen) descriptors — no posting-sized upload on either
    build path."""
    P4 = n_positions // 4
    with jax.named_scope("build.cube_row_targets"):
        R = starts.shape[0]
        lane = jnp.arange(n_lanes, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(cum, lane, side="right") - 1,
                       0, R - 1).astype(jnp.int32)
        src = jnp.clip(starts[row] + lane - cum[row], 0,
                       payload.shape[0] - 1)
        dv = docc[src]
        occ = (dv & _U32(0xF)).astype(jnp.int32)
        dxi = (dv >> 4).astype(jnp.int32)
        dst = jnp.where(lane < cum[-1],
                        (row * n_positions + occ) * D + dxi, total)
    with jax.named_scope("build.cube_row_scatter"):
        flat = jnp.zeros((total,), _U32).at[dst].set(payload[src],
                                                     mode="drop")
    with jax.named_scope("build.cube_quarter_rows"):
        return flat.reshape(total // (P4 * D), P4, D)


# ---------------------------------------------------------------------------
# host-facing results
# ---------------------------------------------------------------------------


@dataclass
class DeviceBuild:
    """One derive-stage result: small directory tables fetched to host
    (exact host-pipeline dtypes), heavy columns still in HBM."""

    n: int                    # stored postings (post store-cap)
    n_pairs: int              # (term, doc) pairs
    dir_termids: np.ndarray   # uint64 [T]
    df: np.ndarray            # int64 [T] distinct-doc counts (pre-cap)
    dir_dstart: np.ndarray    # int64 [T+1]
    dir_pstart: np.ndarray    # int64 [T+1]
    cols: dict                # device columns, padded to the bucket
    # base-only (None for delta folds):
    base_docids: np.ndarray | None = None   # uint64 [Db]
    h_doc_col: np.ndarray | None = None     # int32 [n_pairs]


def _u64(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def _bslice(col, count: int, quantum: int):
    """Device-side slice to the bucketed length before a host fetch —
    bucketed shapes keep the eager-slice compile set bounded while
    shipping ~count elements instead of the whole working bucket."""
    return col[:min(_bucket(max(count, 1), quantum), col.shape[0])]


def fit(col, size: int):
    """Fit a derived device column to an exact tail capacity (columns
    are zero past their counters, so both directions preserve the
    host ``_pad_col`` zero-padding convention)."""
    n = col.shape[0]
    if n >= size:
        return col[:size]
    return jnp.concatenate([col, jnp.zeros(size - n, col.dtype)])


def _fetch_dir(out, counters, quantum: int):
    """Directory tables + counters → host, in host-pipeline dtypes."""
    nk, n_pairs, n_terms = (int(x) for x in counters)
    tid_lo, tid_hi, df, dd, dp = (np.asarray(_bslice(out[k], n_terms,
                                                     quantum))
                                  for k in ("dir_tid_lo", "dir_tid_hi",
                                            "df", "dir_dstart",
                                            "dir_pstart"))
    return nk, n_pairs, dict(
        dir_termids=_u64(tid_lo, tid_hi)[:n_terms],
        df=df[:n_terms].astype(np.int64),
        dir_dstart=np.r_[dd[:n_terms], n_pairs].astype(np.int64),
        dir_pstart=np.r_[dp[:n_terms], nk].astype(np.int64))


def build_base(run_keys: list[np.ndarray], put,
               quantum: int = COL_QUANTUM) -> DeviceBuild | None:
    """Merge + derive the base columns from the Rdb runs' key arrays
    (oldest → newest, the merge_batches recency order). Returns None
    when the merged base is empty (caller keeps its empty-branch
    handling). ``put`` is the caller's device-pinning ``device_put``."""
    total = sum(len(k) for k in run_keys)
    if total == 0:
        return None
    N = _bucket(total, quantum)

    # plain concatenate + bit-split staging (the only host work; the
    # host-sort lint rule keeps every ordering operation on-device)
    n0 = np.concatenate([k["n0"] for k in run_keys]).astype(np.uint32)
    n1 = np.concatenate([k["n1"] for k in run_keys])
    n2 = np.concatenate([k["n2"] for k in run_keys])
    rec = np.concatenate([np.full(len(k), i, np.uint32)
                          for i, k in enumerate(run_keys)])

    def stage(a):
        return put(np.concatenate(
            [a.astype(np.uint32, copy=False),
             np.zeros(N - total, np.uint32)]))

    out = _base_program(
        stage(n0),
        stage(n1 & np.uint64(0xFFFFFFFF)), stage(n1 >> np.uint64(32)),
        stage(n2 & np.uint64(0xFFFFFFFF)), stage(n2 >> np.uint64(32)),
        stage(rec), np.int32(total))
    n_merged, n_docs = (int(x) for x in out["base_counters"])
    if n_merged == 0:
        return None
    nk, n_pairs, dirs = _fetch_dir(out, out["counters"], quantum)
    bd_lo = np.asarray(_bslice(out["bd_lo"], n_docs, quantum))
    bd_hi = np.asarray(_bslice(out["bd_hi"], n_docs, quantum))
    h_doc = np.asarray(_bslice(out["doc_col"], n_pairs, quantum))
    g_stats.count("build.device_base")
    if devwatch.enabled():
        # transient ingest staging in the HBM ledger — the consumer
        # (devindex refresh) drops the slice once fit() folded the
        # columns into the resident plane
        devwatch.note_columns("(ingest)", "build", out)
    return DeviceBuild(
        n=nk, n_pairs=n_pairs, dir_termids=dirs["dir_termids"],
        df=dirs["df"], dir_dstart=dirs["dir_dstart"],
        dir_pstart=dirs["dir_pstart"], cols=out,
        base_docids=_u64(bd_lo, bd_hi)[:n_docs],
        h_doc_col=h_doc[:n_pairs].copy())


def build_delta(fp_: dict, docidx: np.ndarray, put,
                quantum: int = COL_QUANTUM) -> DeviceBuild | None:
    """Sort + derive the delta tail from the memtable positives
    (fields unpacked, docidx already assigned against the base docid
    directory — the cheap O(memtable) host prep stays on host)."""
    m = len(docidx)
    if m == 0:
        return None
    N = _bucket(m, quantum)

    def stage(a, dt=np.uint32):
        return put(np.concatenate(
            [a.astype(dt, copy=False), np.zeros(N - m, dt)]))

    t = fp_["termid"]
    out = _delta_program(
        stage(t & np.uint64(0xFFFFFFFF)), stage(t >> np.uint64(32)),
        stage(docidx, np.int32), stage(fp_["hashgroup"]),
        stage(fp_["densityrank"]), stage(fp_["wordspamrank"]),
        stage(fp_["wordpos"]), stage(fp_["siterank"]),
        stage(fp_["langid"]), np.int32(m))
    nk, n_pairs, dirs = _fetch_dir(out, out["counters"], quantum)
    g_stats.count("build.device_delta")
    if devwatch.enabled():
        devwatch.note_columns("(ingest)", "build", out)
    return DeviceBuild(
        n=nk, n_pairs=n_pairs, dir_termids=dirs["dir_termids"],
        df=dirs["df"], dir_dstart=dirs["dir_dstart"],
        dir_pstart=dirs["dir_pstart"], cols=out)


def doc_meta(sr_tab, dl_tab, dv: DeviceBuild):
    """Apply first-posting-per-doc siterank/langid onto [D_cap] tables
    (zeros for a base build, the resident tables for a delta fold)."""
    return _doc_meta(sr_tab, dl_tab, dv.cols["docidx"],
                     dv.cols["siterank"], dv.cols["langid"],
                     np.int32(dv.n))


def offset_runstarts(dv: DeviceBuild, offset: int, size: int):
    """Delta run starts rebased onto the combined column ([Nb, Nb+n2))
    with the pad rows kept zero — the host rs2 = Nb + runstart2 line."""
    rs = fit(dv.cols["rs"], size)
    live = jnp.arange(size, dtype=jnp.int32) < np.int32(dv.n_pairs)
    return jnp.where(live, rs + np.int32(offset), 0)
