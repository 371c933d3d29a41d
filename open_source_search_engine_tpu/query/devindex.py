"""Device-resident index — two-phase pruned search, the shard's postings
and per-(term, doc) impact bounds live in HBM.

This is the SURVEY §7 architecture plus the reference's own pruning idea
compiled into one XLA program. The reference never scores every docid:
``intersectLists10_r`` computes a cheap ``maxPossibleScore`` per docid and
skips docids that cannot beat the TopTree floor (``Posdb.cpp:6052``; the
"pre-advance" pruning around ``docIdLoop:`` 6137). On a TPU the same idea
becomes two dense phases:

* **Phase 1 — candidates.** Per term group, accumulate a per-doc score
  *upper bound* over the whole doc space ``[T, D]``: precomputed
  per-(term, doc) **impact columns** (the hashgroup-deduped sum of
  position scores — an admissible bound on the group's single-term
  score, and exact for docs with ≤ MAX_TOP distinct hashgroups) are
  added — via plain vectorized adds for high-df terms kept as dense
  ``[V, D]`` rows, and one fused gather+scatter for sparse/delta terms.
  Base and delta accumulate separately so the dead-doc vector masks
  only base contributions (re-adds serve from the delta; tombstones
  that no longer match the base still kill the doc). Boolean
  intersection (every required group present, no negative present —
  ``Msg39``'s early-outs) plus the min-over-groups/pairs bound yields
  an admissible per-doc upper bound; ``approx_max_k`` picks the top-κ
  candidates. The exact match count and the exact max bound among
  *non*-selected docs come out of the same pass, so pruning is
  verifiable.
* **Phase 2 — exact.** For the κ candidates only, gather the real
  postings (run starts come from precomputed ``runstart|count`` columns
  — no per-query binary search, no big scatter) into the dense
  ``[T, P, κ]`` position cube and score with the exact docIdLoop
  semantics (scorer.min_scores — identical math to the host-packed
  path, so parity holds by construction).
* **Escalation.** If the max bound among non-candidates exceeds the
  k-th exact score (beyond a 1e-4 tie tolerance), rerun with κ×4
  (rare: bounds are tight). This makes the pruning *lossless* — the
  TPU analog of TopTree's floor check, and of the reference's recall
  re-loop (``Msg40.cpp:2117``).
* **Full-cube path (F2).** Queries whose every required group is a
  high-df term defeat bound pruning — the intersection is most of the
  corpus and pair bounds can't rank it (the pair score's distance term
  is unknowable without positions). The reference grinds these with
  its per-docid loop; here they route to a second kernel that scores
  the WHOLE doc axis exactly: the heaviest terms' position cubes are
  **materialized at build time** as [P, D] rows (plain slices at query
  time — zero gather; resident as ONE array of quarter rows,
  ``d_cube`` [Vc·4, P/4, D_cap] uint32, the form the fused FD kernel
  DMAs from, so no wave program copies or relayouts the cube: a
  term's [P, D] row is four consecutive quarter rows, and the last
  slot stays all-zero), smaller sublists (bigrams, deltas) scatter
  their postings in at posting granularity, and the same
  scorer.min_scores runs over [T, P, D]. Dense full-lane compute is
  exactly what the VPU is good at — no pruning needed, no escalation
  ladder, still bit-parity with the host path.

Why this shape: on v5e, scalar gather runs ~60 Melem/s and scatter ~10
Melem/s, while dense row ops and 128-lane block gathers run 10-100×
faster. So the per-query work that scales with the corpus (phase 1) uses
only dense ops + one bounded scatter, and the slow scalar gathers are
confined to phase 2's κ·T·P lanes. The former design (docid-tile scan
with per-tile gather+rank+scatter) paid the scatter price on every
posting of every tile and recompiled per posting-length bucket; this one
has no per-query shape that depends on posting-list length.

Admissibility of the bounds (what makes pruning exact):

* group single-term score = Σ of the top-MAX_TOP hashgroup-deduped
  position scores ≤ the stored impact (Σ over ALL mapped-hashgroup
  maxima + every inlink-text occurrence; synonym sublists score ×0.90²
  at query time — bounded by 1);
* pair score ≤ BASE·maxposw_i·maxposw_j·fw_i·fw_j (min distance term
  ≥ 1 after the qdist adjustment) and BASE·maxposw² ≤ impact, so
  √(impact_i·fw_i²·impact_j·fw_j²) bounds every pair term;
* siterank/language multipliers are exact (dense per-doc columns);
* the final ×(1+1e-5) guards float reassociation (the escalation check
  allows 1e-4 so exact ties don't escalate forever).

Incremental updates (SURVEY §7 hard part (d)): the base columns build
once per Rdb run-set move (dump/merge); a memtable change rewrites only
the delta tail of the preallocated device columns via donated
dynamic-update-slice — O(memtable) transfer, no O(corpus) copies, no
double residency. Document frequencies stay exact under deletes via the
tombstone-pair subtraction (the Msg36/37 termfreq role).

Capacity: run starts are full int32 column offsets (counts ride a
separate uint8 column), so the pack limit is 2^31 stored postings and
HBM binds first — a 16 GB v5e holds roughly 1.3M web pages' columns
plus dense/cube rows. Beyond that the corpus must shard
(``parallel/``), same as the reference's per-host index splits.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..build import devbuild
from ..index import clusterdb as clusterdb_mod
from ..index import posdb
from ..index.collection import Collection
from ..index.rdblite import merge_batches
from ..utils import devwatch, jitwatch, trace
from ..utils.log import get_logger
from ..utils.stats import g_stats
from . import devcheck, weights
from .compiler import SUB_SYNONYM, QueryPlan, compile_query
from .packer import (IMPACT_SCALE, MAX_POSITIONS, T_FLOOR, TABLE_SIZE,
                     WIDE_T, _bucket, _pad1, demote_impacts, group_flags,
                     pack_payload, pad_table)
from .scorer import final_multipliers, min_scores, presence_table_ok

log = get_logger("devindex")

# the device layer is the first import on every jit path — turning the
# watcher on here means OSSE_JITWATCH=1 covers tests, bench, and serve
# without each entry point opting in
jitwatch.maybe_enable()
devwatch.maybe_enable()

#: bounded wave-histogram vocabulary. The per-round wave stat used to
#: be built with an f-string over (kind-combo, wave count) — one
#: histogram per distinct count, unbounded cardinality (the osselint
#: ``stats-cardinality`` rule now bans that spelling). This table IS
#: the bound: kind combos × count buckets, fixed at import.
_WAVE_NBUCKETS = (1, 2, 4, 8)
_WAVE_STAT = {(k, n): f"devindex.wave_{k}_n{n}"
              for k in ("f1", "f2", "f1+f2") for n in _WAVE_NBUCKETS}


#: an F1 wave's κ rung on the same histogram family (one record a
#: wave, over its round's fetch interval): the ladder's three rungs and
#: everything above them — four names, fixed at import
_WAVE_KAPPA_STAT = {k: f"devindex.wave_f1_k{k}"
                    for k in (256, 2048, 8192, "max")}
#: which programs a span of time dispatched, as counters: a program key
#: takes the next slot at its first dispatch (the last slot takes every
#: key past the table), and every dispatch counts its slot — the number
#: of slots that moved is the number of distinct programs (a lower
#: bound where several indexes serve)
_PROGRAM_SLOT_STAT = tuple(f"devindex.program_slot.{i:02d}"
                           for i in range(64))
#: a query each, where ``route_counts`` is bumped (first route only);
#: ``.t8``: of them, the queries of the ``WIDE_T`` bucket
_ROUTE_STAT = {r: f"devindex.route.{r}" for r in ("f1", "fd", "f2")}
_ROUTE_STAT_WIDE = {r: f"devindex.route.{r}.t8" for r in ("f1", "fd", "f2")}


def _f1_rows(mrd: int, mrs: int, mls: int, upper: bool = False,
             wide: bool = False) -> tuple[int, int, int]:
    """(Rd, Rs, Lsp) of an F1 wave whose widest rider has ``mrd`` dense
    rows, ``mrs`` sparse rows and a longest sparse run of ``mls``: the
    first tier of the chain that covers all three (``upper``: a rung
    above the first, which rides the long-run tiers only; ``wide``: a
    wave of ``WIDE_T`` groups, which rides the top tier only)."""
    chain = F1_TIERS[-1:] if wide else (
        F1_UPPER_TIERS if upper else F1_TIERS)
    for t in chain:
        if mrd <= t[0] and mrs <= t[1] and mls <= t[2]:
            return t
    # past 16 rows: outside the enumerated set, bucketed as before
    return (16 if mrd <= 16 else _bucket(mrd, 64),
            16 if mrs <= 16 else _bucket(mrs, 64), LSP_MAX)


def _wave_nbucket(n: int) -> int:
    for b in _WAVE_NBUCKETS:
        if n <= b:
            return b
    return _WAVE_NBUCKETS[-1]

#: sparse gather lane tiles (length-bucketed termlist tiles, SURVEY
#: §7 stage-8) are the third number of an F1 tier below: waves whose
#: longest sparse run is short ride a short tile instead of paying the
#: full 2048-lane gather per row — the padding bytes were most of the
#: sparse HBM traffic for everyday queries (the dense threshold
#: D_cap//64 keeps runs under the top)
KAPPA_FLOOR = 256  # phase-2 candidate count

#: THE CLOSED F1 PROGRAM SPACE. A ``_two_phase`` program is keyed on
#: (B, Rd, Rs, Lsp, κ, k2) plus T and three flags, and a wave takes the
#: MAXIMUM of its riders' row counts and run lengths — so two queries
#: that each ran alone can form a key neither reached. Invariant: for
#: any set of F1 plans ``_issue_waves`` puts into one ``_run_batch``
#: call, of queries of up to T_FLOOR plain words, or of WIDE_T,
#: with the default filter and sort, the key is a member of
#: ``f1_programs()``, which the index enumerates from its D_cap alone.
#: How: the row shape (Rd, Rs, Lsp) is the first TIER of a chain that
#: covers the wave (a chain, so the join of any riders is a tier); a
#: wave holds four plans (B = 4: a fuller batch is more waves, not a
#: wider program); on the ladder's three rungs phase 2 scores every
#: selected candidate (k2 = κ, for single-group plans too); and a rung
#: above the first rides the two long-run tiers only (escapees are
#: few). Nine programs at T_FLOOR, sized by what a server can compile
#: at start-up (PERF.md, Findings: ~120 s cold; each wider B
#: bucket, each separate k2 would be as many again). A wave of five
#: to eight words (T = WIDE_T) rides the top tier alone, one
#: program a rung: its words and their bigrams hold at most 16 rows
#: where each sublist is one run, and ``warm_f1`` takes its three
#: programs with the nine. Its key carries T; the T_FLOOR keys do not.
#: Outside the set, and shaped as before: nine words and more,
#: boolean tables, filters, sorts, κ above 32·KAPPA_FLOOR up to the
#: terminal D_cap, and plans past 16 rows
#: (``devindex.f1.key_outside_set`` counts their dispatches).
F1_TIERS = ((4, 2, 128), (4, 2, 512), (4, 4, 512), (4, 4, 2048),
            (16, 16, 2048))
#: the tiers a rung above the first may ride (the last two)
F1_UPPER_TIERS = F1_TIERS[-2:]
F1_B = 4
#: the κ ladder's rungs, in KAPPA_FLOORs; above the last the rung is
#: the need's own bucket, up to D_cap
F1_RUNGS = (1, 8, 32)
DOC_UPD_FLOOR = 64

#: doc-capacity quantum (D_cap bucket unit)
DOC_QUANTUM = 2048

#: HBM budget for dense [V, D_cap] impact+runstart rows (8 bytes/doc/
#: term). Sized so that at web-shard scale (~500k docs) the heaviest
#: ~400 terms are dense, and at 100k docs EVERY df>tau term is — a
#: sparse run that should have been dense pays scalar-scatter for its
#: whole doc run on every query, measured as THE dominant query cost
DENSE_BUDGET_BYTES = 1536 << 20

#: minimum df for a term to earn a dense impact row
DENSE_MIN_DF = 1024

#: sparse doc-runs are CHUNKED to this many lanes per row, so the lane
#: bucket is a compile-time constant (no per-query Lsp recompiles) and
#: pad lanes never exceed one chunk per term — unbudgeted big terms
#: degrade linearly instead of rectangularly
LSP_MAX = 2048

#: HARD CAP for materialized [P, D_cap] cube rows (P·4 bytes/doc/term)
#: — the actual budget is adaptive: after columns + dense rows claim
#: their bytes, the cube gets what HBM can spare (more cube rows →
#: more corpus-wide drivers resolve through the flat-cost direct
#: kernel instead of the assembling F2)
CUBE_BUDGET_BYTES = 5 << 30
#: usable HBM for the resident set (v5e 16 GB minus XLA/runtime slack)
HBM_USABLE_BYTES = 13 << 30
#: head-room reserved for wave intermediates next to the resident set
WAVE_RESERVE_BYTES = 5 << 29

#: direct-kernel scatter tail budget: total non-cube postings a query
#: may scatter into its quarter-built plane before falling back to the
#: generic F2 (scalar scatter runs ~10 Melem/s — keep the tail small)
FD_SCATTER_MAX_LANES = 32768
FD_SCATTER_MAX_ROWS = 32

#: routing: drivers at or below this df use phase-1 pruning (F1);
#: bigger drivers go to the full-cube kernel (F2), whose cost is flat
#: in the driver size (F1's phase-2 gathers scale with κ ≥ driver_df —
#: measured 4× slower at κ=8192 than the whole F2 kernel)
CUBE_MIN_DF = 2048

#: F2 eligibility: non-cube sublists must scatter at most this many
#: postings (the per-row scatter lane bucket cap)
F2_SCATTER_MAX = 16384
F2_LPOST_FLOOR = 4096
F2_B_FLOOR = 4
RC_FLOOR = 4
RP_FLOOR = 4

#: posting/doc column padding quantum
COL_QUANTUM = 1 << 15

#: run starts and counts live in SEPARATE columns (int32 runstart +
#: uint8 count) — the former rs<<5|cnt int32 pack capped a shard at
#: 2^26 stored postings (~500k pages); split, the pack limit is the
#: int32 index space and HBM binds first (~1.3M pages on a 16 GB v5e)
_MAX_POSTINGS = 1 << 31
#: posting doc+occurrence pack: docidx<<4 | occ in one uint32 (occ <
#: MAX_POSITIONS = 16 → 4 bits; doc capacity 2^28) — one gather feeds
#: both fields in the F2/FD scatter paths
_OCC_BITS = 4
_OCC_MASK = 15

#: escalation tie tolerance (× the 1e-5 admissibility inflation)
_TIE_TOL = 1.0001


def _posscore_np(f: dict[str, np.ndarray]) -> np.ndarray:
    """Per-posting single-term score (BASE · posw², the initWeights
    tables — Posdb.cpp:1105-1252), vectorized numpy for build time."""
    hg = f["hashgroup"]
    hgw = weights.HASH_GROUP_WEIGHTS[hg]
    denw = weights.DENSITY_WEIGHTS[f["densityrank"]]
    spamw = np.where(hg == posdb.HASHGROUP_INLINKTEXT,
                     weights.LINKER_WEIGHTS[f["wordspamrank"]],
                     weights.WORD_SPAM_WEIGHTS[f["wordspamrank"]])
    posw = hgw * denw * spamw
    return weights.BASE_SCORE * posw * posw


def _impacts_np(f: dict[str, np.ndarray], termids: np.ndarray,
                docidx: np.ndarray, runstart: np.ndarray) -> np.ndarray:
    """EXACT per-(term, doc) single-term score (pre-freq-weight): Σ over
    the top-MAX_TOP of {per-mapped-hashgroup position maxima} ∪ {every
    inlink-text occurrence individually} — exactly the candidate set
    getSingleTermScore tops-and-sums (Posdb.cpp:3087), exactly cut.
    Equal (mod float association) to what scorer.min_scores computes
    from the stored positions, so (a) it is an admissible AND tight
    phase-1 bound, and (b) the direct-cube kernel can use it AS the
    single-term score without touching positions."""
    n = len(termids)
    if n == 0:
        return np.empty(0, np.float32)
    ps = _posscore_np(f)
    mhg = weights.MAPPED_HASHGROUP[f["hashgroup"]].astype(np.int8)
    is_inlink = f["hashgroup"] == posdb.HASHGROUP_INLINKTEXT
    # candidate pool per (term, doc): one max per non-inlink mapped
    # hashgroup + each inlink occurrence individually. Build it by
    # collapsing non-inlink (term, doc, mhg) groups to their max and
    # keeping inlink rows as-is, then rank within (term, doc).
    o = np.lexsort((mhg, docidx, termids))
    ps_o, mh_o, il_o = ps[o], mhg[o], is_inlink[o]
    t_o, d_o = termids[o], docidx[o]
    gch = np.ones(n, bool)
    gch[1:] = ((t_o[1:] != t_o[:-1]) | (d_o[1:] != d_o[:-1])
               | (mh_o[1:] != mh_o[:-1]))
    # candidates: non-inlink groups contribute their first-row slot
    # (value = group max); inlink rows contribute every row
    gid = np.cumsum(gch) - 1
    gstart = np.nonzero(gch)[0]
    gmax = np.maximum.reduceat(ps_o, gstart)
    cand_mask = il_o | gch
    cval = np.where(il_o, ps_o, gmax[gid])[cand_mask]
    ct = t_o[cand_mask]
    cd = d_o[cand_mask]
    m = len(cval)
    # rank candidates within each (term, doc) pair (descending) and
    # zero everything past MAX_TOP before the pair sum
    pch = np.ones(m, bool)
    pch[1:] = (ct[1:] != ct[:-1]) | (cd[1:] != cd[:-1])
    pstart = np.nonzero(pch)[0]
    pair_id = np.cumsum(pch) - 1               # candidate → owning pair
    order2 = np.lexsort((-cval, pair_id))
    ranked = np.empty(m, np.int64)
    pos_in_pair = np.arange(m) - pstart[pair_id[order2]]
    ranked[order2] = pos_in_pair
    cval_cut = np.where(ranked < weights.MAX_TOP, cval, 0.0)
    imp = np.add.reduceat(cval_cut, pstart)
    assert len(imp) == len(runstart)
    # tiny floor keeps zero-weight hashgroups present-but-worthless
    return np.maximum(imp, 1e-30).astype(np.float32)


def _occ_ranks(termids: np.ndarray, docs: np.ndarray) -> np.ndarray:
    """Occurrence rank within each (termid, doc) run of the sorted
    columns — vectorized running-max scan (the mini-merge slot count)."""
    n = len(termids)
    if n == 0:
        return np.empty(0, np.int64)
    newpair = np.ones(n, bool)
    newpair[1:] = (termids[1:] != termids[:-1]) | (docs[1:] != docs[:-1])
    idx = np.arange(n)
    first = np.maximum.accumulate(np.where(newpair, idx, 0))
    return idx - first


def _term_dfs(termids: np.ndarray, newpair: np.ndarray):
    """(dir_termids, dir_start, df): per-term run bounds + distinct-doc
    counts over sorted columns (the Msg36 termfreq precompute)."""
    n = len(termids)
    if n == 0:
        return (np.empty(0, np.uint64), np.zeros(1, np.int64),
                np.empty(0, np.int64))
    tchange = np.ones(n, bool)
    tchange[1:] = termids[1:] != termids[:-1]
    starts = np.nonzero(tchange)[0]
    df = np.add.reduceat(newpair.astype(np.int64), starts)
    return termids[starts].copy(), np.r_[starts, n].astype(np.int64), df


def _pad_col(a: np.ndarray, size: int) -> np.ndarray:
    out = np.zeros(size, a.dtype)
    out[: len(a)] = a
    return out


def _env_int(name: str, default: int) -> int:
    """Env-overridable tuning constant — tests and the multichip dryrun
    scale the dense/cube thresholds down so TINY per-shard corpora still
    build dense+cube rows and exercise every kernel route (production
    defaults are sized for real shards)."""
    import os
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


@partial(jax.jit, donate_argnums=0)
def _write_tail(buf, tail, offset):
    """Donated in-place rewrite of the delta tail of a device column."""
    return jax.lax.dynamic_update_slice(buf, tail, (offset,))


def _block_topn(x, n_sel: int, per_block: int = 8):
    """Top-``per_block``-per-block candidate selection: (vals [n_sel],
    idx [n_sel], missed_max) — n_sel/per_block blocks, the best
    per_block docs of each selected, ``missed_max`` = the best value
    NOT selected ((per_block+1)-th best over any block).

    This replaces ``lax.top_k``/``approx_max_k`` for candidate
    selection: both lower to sort-like programs that cost 300 ms-2.4 s
    per batch on a [B, 131072] score axis (measured), while this is a
    handful of reshaped max-reduces (~2 ms). per_block sets the
    collision robustness: selecting k winners across nb blocks misses
    only when one block holds > per_block of them — at per_block=8 and
    k ≈ n_sel/4 that's a ≲1% event (Poisson tail), vs near-certain at
    per_block=2 with few blocks. The caller compares ``missed_max``
    against its result floor and escalates with more blocks — the same
    lossless pruning contract as everywhere else."""
    D = x.shape[0]
    nb = max(n_sel // per_block, 1)
    while D % nb:  # D is a power-of-two bucket, but stay safe
        nb //= 2
    R = D // nb
    xb = x.reshape(nb, R)
    iota = jnp.arange(R, dtype=jnp.int32)[None, :]
    base = jnp.arange(nb, dtype=jnp.int32) * R
    vals_l, idx_l = [], []
    cur = xb
    for t in range(per_block):
        m = jnp.max(cur, axis=1)
        a = jnp.argmax(cur, axis=1).astype(jnp.int32)
        vals_l.append(m if t == 0 else jnp.maximum(m, 0.0))
        idx_l.append(base + a)
        cur = jnp.where(iota == a[:, None], -jnp.inf, cur)
    missed = jnp.maximum(jnp.max(cur), 0.0)
    return (jnp.concatenate(vals_l), jnp.concatenate(idx_l), missed)


def _block_top2(x, n_sel: int):
    return _block_topn(x, n_sel, per_block=2)


@partial(jax.jit, static_argnames=("V", "D", "n_lanes"))
def _build_dense_rows(d_doc, d_imp, d_rs, d_cnt, starts, cum,
                      V: int, D: int, n_lanes: int):
    """Dense [V, D] impact + runstart + count rows, built by one
    flattened scatter over the doc-pair columns. Lane → row via
    searchsorted on the cumulative-length table; everything stays on
    device — the host ships only (starts, cum), a few KB."""
    with jax.named_scope("build.dense_row_targets"):
        R = starts.shape[0]
        lane = jnp.arange(n_lanes, dtype=jnp.int32)
        row = jnp.clip(jnp.searchsorted(cum, lane, side="right") - 1,
                       0, R - 1).astype(jnp.int32)
        src = jnp.clip(starts[row] + lane - cum[row], 0,
                       d_doc.shape[0] - 1)
        valid = lane < cum[-1]
        doc = d_doc[src].astype(jnp.int32)
        # dst fits int32: V·D ≤ DENSE_BUDGET/7 < 2^31
        dst = jnp.where(valid, row * D + doc, V * D)
    with jax.named_scope("build.dense_row_scatter"):
        imp = jnp.zeros((V * D,), d_imp.dtype).at[dst].set(
            d_imp[src], mode="drop")
        rs = jnp.zeros((V * D,), jnp.int32).at[dst].set(
            d_rs[src], mode="drop")
        cnt = jnp.zeros((V * D,), jnp.uint8).at[dst].set(
            d_cnt[src], mode="drop")
        return imp.reshape(V, D), rs, cnt


class _DeltaOverflow(Exception):
    def __init__(self, needed_docs: int = 0, needed_cols: int = 0):
        self.needed_docs = needed_docs
        self.needed_cols = needed_cols


@dataclass
class ResidentPlan:
    """Host-computed execution plan for one query (all tiny arrays)."""

    # dense rows: term's doc run lives as a dense [D_cap] impact row
    d_slot: np.ndarray       # int32 [Rd] dense matrix row (-1 = pad)
    d_group: np.ndarray      # int32 [Rd]
    d_base: np.ndarray       # int32 [Rd] slot base within the group's P
    d_quota: np.ndarray      # int32 [Rd]
    d_syn: np.ndarray        # uint32 [Rd]
    # sparse rows: contiguous run of the doc/impact/runstart columns
    s_start: np.ndarray      # int32 [Rs] absolute offset into doc cols
    s_len: np.ndarray        # int32 [Rs]
    s_group: np.ndarray      # int32 [Rs]
    s_base: np.ndarray       # int32 [Rs]
    s_quota: np.ndarray      # int32 [Rs]
    s_syn: np.ndarray        # uint32 [Rs]
    s_isbase: np.ndarray     # bool [Rs] (base postings dead-mask)
    # full-cube (F2) rows: materialized cube slices + posting scatters
    c_slot: np.ndarray       # int32 [Rc] cube matrix row (-1 = pad)
    c_dslot: np.ndarray      # int32 [Rc] dense row (count source)
    c_group: np.ndarray      # int32 [Rc]
    c_base: np.ndarray       # int32 [Rc]
    c_quota: np.ndarray      # int32 [Rc]
    c_syn: np.ndarray        # uint32 [Rc]
    p_start: np.ndarray      # int32 [Rp] absolute posting offset
    p_len: np.ndarray        # int32 [Rp]
    p_group: np.ndarray      # int32 [Rp]
    p_base: np.ndarray       # int32 [Rp]
    p_quota: np.ndarray      # int32 [Rp]
    p_syn: np.ndarray        # uint32 [Rp]
    p_isbase: np.ndarray     # bool [Rp]
    # per-group query state
    freq_weight: np.ndarray  # float32 [T]
    required: np.ndarray     # bool [T]
    negative: np.ndarray     # bool [T]
    scored: np.ndarray       # bool [T]
    counts: np.ndarray       # bool [T] groups entering the min-score
    table: np.ndarray        # bool [TABLE_SIZE] boolean truth table
    qlang: int
    matchable: bool
    driver_df: int = 0       # min required-group df (routes F1 vs F2)
    kappa_min: int = 0       # escalation floor (set on a pruning miss)
    k2_min: int = 0          # phase-2 width floor (escalates with κ so
    #                          the terminal rung scores everything and
    #                          the ladder stays lossless)
    #: direct-cube (FD) eligibility: every group's contributing runs
    #: are base cube rows whose slot_plan layout is quarter-aligned
    #: (1 sublist = full row, 2 = half+half, 3 = half+quarter+quarter).
    #: The group's [P, D] plane is then FOUR quarter-row gathers from
    #: the resident cube — no per-query cube assembly at all.
    direct_ok: bool = False
    g_quarter: np.ndarray | None = None  # int32 [T, 4] absolute quarter
    g_qsyn: np.ndarray | None = None     # uint32 [T, 4] synonym flags
    #: True only for boolean queries — non-boolean waves compile the
    #: truth-table gate out (its [D]-wide gather costs ~140 ms/wave)
    has_table: bool = False
    #: numeric range constraints / sort override (gbmin:/gbmax:/
    #: gbsortby: — waves group by identical specs; the [D] filter and
    #: sort columns are per-wave kernel args)
    filters: tuple = ()
    sortby: tuple | None = None
    #: shift applied to sort keys (keys must stay positive for the
    #: match gate) — the MESH layer passes the cross-shard minimum so
    #: per-shard keys stay comparable under the Msg3a merge
    sort_base: float = 0.0
    #: number of scored∧required groups (the single definition every
    #: routing/k2/κ decision keys on)
    n_scored: int = 0


@dataclass
class PendingBatch:
    """One issued-but-unfetched batch: waves are on the device queue,
    no output has been synced. Produced by ``issue_batch`` (pure async
    enqueue), consumed by ``collect_batch`` (the one host sync). The
    resident serving loop holds up to two of these so batch N+1's
    dispatch rides under batch N's compute; ``search_batch`` is the
    same two halves back-to-back, so the paths cannot diverge."""

    plans: list
    results: list
    waves: list
    k_req: int
    k2v: int
    f2_nsel: int
    bmax: int
    topk: int


class DeviceIndex:
    """One collection's postings + impact bounds, resident in HBM."""

    def __init__(self, coll: Collection, max_positions: int = MAX_POSITIONS,
                 device=None):
        #: device pinning: a mesh of chips serves one shard per chip —
        #: every resident array and kernel dispatch for this index
        #: stays on ``device`` (jit follows committed operands), so N
        #: shards execute concurrently on N chips
        self.device = device
        self.coll = coll
        if max_positions > (1 << _OCC_BITS):
            raise ValueError(
                f"max_positions > {1 << _OCC_BITS} overflows the 4-bit "
                "occurrence field of the docc pack")
        self.P = max_positions
        self._built_version = -1
        self._base_fp = None
        self.full_rebuilds = 0    # O(corpus) base rebuilds (run-set moved)
        self.delta_rebuilds = 0   # O(memtable) delta-only refreshes
        self.escalations = 0      # phase-2 κ escalations (pruning misses)
        #: kernel-route observability: queries initially routed to the
        #: two-phase (f1), direct-cube (fd) and generic full-cube (f2)
        #: kernels (escalation reruns not counted)
        self.route_counts = {"f1": 0, "fd": 0, "f2": 0}
        #: dispatches by (program name, shape bucket), counted where
        #: every wave program goes through (``_costed``)
        self.dispatches: dict[tuple, int] = {}
        self._slot_of: dict[tuple, str] = {}    # key -> its slot's counter
        #: ``warm_f1`` ran on this index (its programs are this
        #: process's; a fresh index after a rebuild has the same shapes
        #: unless D_cap or a column bucket moved, and then warms again)
        self._f1_warmed = False
        #: resident-plan cache (the termlist-cache role, RdbCache): the
        #: per-query host planning pass — directory binary searches, df
        #: lookups, slot planning, row layout — repeats byte-identically
        #: for a repeated query until a write moves posdb or fielddb;
        #: generation-keyed on both versions so invalidation is O(1).
        #: Mutations of a cached plan's kappa_min/k2_min escalation
        #: floors are deliberate: a hot query's learned floor persists.
        from ..cache import g_cacheplane
        _coll = coll
        self._plan_cache = g_cacheplane.register(
            f"devindex.plan.{coll.name}", ttl_s=300.0, max_entries=2048,
            gen_fn=lambda: (_coll.posdb.version,
                            _coll.fielddb.rdb.version),
            desc="resident query plans (termlist-cache role)")
        self.refresh()

    def _put(self, a):
        return jax.device_put(a, self.device) if self.device is not None \
            else jax.device_put(a)

    # --- build / refresh -------------------------------------------------

    def refresh(self) -> bool:
        """(Re)build device arrays if the underlying Rdb changed: delta
        only while the run set is stable, full base rebuild when a
        dump/merge moved it (SURVEY §7 hard part (d))."""
        rdb = self.coll.posdb
        if rdb.version == self._built_version:
            return False
        self._sitehash = None  # clusterdb view refreshes lazily
        self._fcols = {}        # fielddb columns re-derive
        self._fswave = {}
        self._docid_sorted = None  # sorted docid view rebuilds
        # content-addressed fingerprint: keys_crc makes a rebuilt run
        # with a coincidentally identical (name, count) miss the cache
        fp = tuple((r.path.name, len(r), r.meta.get("keys_crc"))
                   for r in rdb.runs)
        if fp != self._base_fp:
            self._build_base(fp)
        # the delta can outgrow the doc-capacity headroom AND the
        # preallocated column tails independently — regrow and retry
        min_docs = min_delta = 0
        for _ in range(3):
            try:
                self._build_delta()
                break
            except _DeltaOverflow as e:
                min_docs = max(min_docs, e.needed_docs)
                min_delta = max(min_delta, e.needed_cols)
                self._build_base(fp, min_docs=min_docs,
                                 min_delta=min_delta)
        else:
            self._build_delta()
        self._built_version = rdb.version
        if devwatch.enabled():
            # one registration point covers base, delta and regrow —
            # every rebuild path funnels through here with the final
            # column bindings; the devbuild staging slice is consumed
            # by now, so release it in the same breath
            devwatch.drop("(ingest)", "build")
            devwatch.note_columns(self.coll.name, "devindex",
                                  self._column_map())
        return True

    #: bump when any derived-column computation changes (cache schema)
    _CACHE_SCHEMA = 4  # v4: split rs/cnt columns (2^26 cap lifted)

    def _cache_path(self, fp):
        import hashlib
        h = hashlib.sha1(repr((fp, self.P, self._CACHE_SCHEMA))
                         .encode()).hexdigest()[:16]
        return self.coll.posdb.dir / "devcache" / f"base_{h}.npz"

    def _load_base_cache(self, fp):
        """Derived base columns, cached on disk per run-set fingerprint
        (the expensive host derivation — 25M-posting merge + impact
        bounds — runs once per dump/merge, not once per process; a
        restarted node rebuilds its device mirror at transfer speed)."""
        p = self._cache_path(fp)
        if not p.exists():
            return None
        try:
            z = np.load(p)
            return tuple(z[k] for k in (
                "dir_termids", "base_df", "dir_dstart", "dir_pstart",
                "base_docids", "docidx", "pocc", "payload", "doc_col",
                "imp_col", "rs_col", "cnt_col", "siterank", "langid"))
        except Exception:  # torn write etc. — recompute
            return None

    def _save_base_cache(self, fp, docidx, pocc, payload, doc_col,
                         imp_col, rs_col, cnt_col, siterank,
                         langid) -> None:
        p = self._cache_path(fp)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = p.with_suffix(".tmp.npz")
        np.savez(tmp, dir_termids=self.dir_termids,
                 base_df=self.base_df, dir_dstart=self.dir_dstart,
                 dir_pstart=self.dir_pstart,
                 base_docids=self.base_docids, docidx=docidx, pocc=pocc,
                 payload=payload, doc_col=doc_col, imp_col=imp_col,
                 rs_col=rs_col, cnt_col=cnt_col, siterank=siterank,
                 langid=langid)
        tmp.rename(p)
        # stale fingerprints go only AFTER the new cache landed: a crash
        # mid-savez used to leave NO cache at all, forcing a full
        # rebuild on next boot (the classic swap-order bug)
        for old in p.parent.glob("base_*.npz"):
            if old != p:
                old.unlink()  # only the live fingerprint is useful

    def _postings_overflow(self) -> ValueError:
        """The 2^31-postings runstart pack limit, as a counted,
        admin-visible condition (the /admin/perf shard-split alert) —
        a fleet operator sees the counter before the node boot-loops
        on the raise."""
        g_stats.count("build.postings_overflow")
        return ValueError(
            f"shard exceeds {_MAX_POSTINGS} stored postings "
            "(runstart pack limit) — split the collection "
            "across more shards")

    def _build_base(self, fp, min_docs: int = 0, min_delta: int = 0
                    ) -> None:
        """Base columns from the Rdb's immutable runs (merged, tombstones
        annihilated — the Msg5 read collapsed to one columnar merge),
        plus preallocated delta tails."""
        runs = self.coll.posdb.runs
        P = self.P
        cached = self._load_base_cache(fp)
        dv = None
        if cached is not None:
            (self.dir_termids, self.base_df, self.dir_dstart,
             self.dir_pstart, self.base_docids, docidx, pocc, payload,
             doc_col, imp_col, rs_col, cnt_col, siterank,
             langid) = cached
            n = len(docidx)
            batch = None
        else:
            if devbuild.enabled() and runs:
                # the device ingest plane: merge + derive on-chip, the
                # host NumPy pipeline below stays as oracle + fallback
                try:
                    dv = devbuild.build_base(
                        [r.batch().keys for r in runs], self._put)
                except Exception:
                    log.exception("device base build failed — falling "
                                  "back to the host pipeline")
                    g_stats.count("build.devbuild_fallback")
                    dv = None
            batch = None if dv is not None else (
                merge_batches([r.batch() for r in runs])
                if runs else None)
        if cached is not None:
            pass
        elif dv is not None:
            # columns already live in HBM; only the directory tables,
            # docid map and doc_col came back to host
            self.dir_termids = dv.dir_termids
            self.base_df = dv.df
            self.dir_dstart = dv.dir_dstart
            self.dir_pstart = dv.dir_pstart
            self.base_docids = dv.base_docids
            doc_col = dv.h_doc_col
            n = dv.n
            if n >= _MAX_POSTINGS:
                raise self._postings_overflow()
            docidx = pocc = payload = imp_col = rs_col = cnt_col = None
            siterank = langid = None
        elif batch is not None and len(batch):
            f = posdb.unpack(batch.keys)
            termids, docids = f["termid"], f["docid"]
            occ = _occ_ranks(termids, docids)
            self.dir_termids, _, self.base_df = _term_dfs(termids, occ == 0)
            # store-cap: scoring consumes ≤ P positions per (term, doc),
            # so postings past occurrence P are dead weight in HBM
            keep = occ < P
            pocc = occ[keep].astype(np.uint8)
            f = {k: v[keep] for k, v in f.items()}
            termids, docids = f["termid"], f["docid"]
            if len(termids) >= _MAX_POSTINGS:
                raise self._postings_overflow()
            payload = pack_payload(f)
            self.base_docids = np.unique(docids)
            docidx = np.searchsorted(self.base_docids, docids).astype(
                np.int32)
            n = len(docidx)
            # --- doc-level runs: one entry per (term, doc) pair ---
            newpair = np.ones(n, bool)
            newpair[1:] = (termids[1:] != termids[:-1]) | \
                (docidx[1:] != docidx[:-1])
            runstart = np.nonzero(newpair)[0].astype(np.int64)
            doc_col = docidx[newpair]
            count = np.diff(np.r_[runstart, n])
            imp_col = _impacts_np(f, termids, docidx, runstart)
            rs_col = runstart.astype(np.int32)
            cnt_col = np.minimum(count, P).astype(np.uint8)
            tchange = np.ones(n, bool)
            tchange[1:] = termids[1:] != termids[:-1]
            tstarts = np.nonzero(tchange)[0]
            self.dir_dstart = np.r_[
                np.searchsorted(runstart, tstarts), len(runstart)
            ].astype(np.int64)
            self.dir_pstart = np.r_[tstarts, n].astype(np.int64)
            siterank = f["siterank"].astype(np.int32)
            langid = f["langid"].astype(np.int32)
            self._save_base_cache(fp, docidx, pocc, payload, doc_col,
                                  imp_col, rs_col, cnt_col, siterank,
                                  langid)
        else:
            self.dir_termids = np.empty(0, np.uint64)
            self.base_df = np.empty(0, np.int64)
            self.dir_dstart = np.zeros(1, np.int64)
            self.dir_pstart = np.zeros(1, np.int64)
            self.base_docids = np.empty(0, np.uint64)
            docidx = np.empty(0, np.int32)
            pocc = np.empty(0, np.uint8)
            payload = np.empty(0, np.uint32)
            doc_col = np.empty(0, np.int32)
            imp_col = np.empty(0, np.float32)
            rs_col = np.empty(0, np.int32)
            cnt_col = np.empty(0, np.uint8)
            siterank = langid = np.empty(0, np.int32)
            n = 0

        Db = len(self.base_docids)
        headroom = max(1024, Db // 4)
        self.D_cap = _bucket(max(Db + headroom, min_docs, 1), DOC_QUANTUM)
        if self.D_cap > (1 << 28):
            # docc pack ships docidx in the high 28 bits of a uint32
            raise ValueError(
                "docc pack caps a shard at 2^28 docs — shard the corpus")

        # --- doc meta table (first posting per doc supplies siterank/
        # langid — reference getSiteRank(miniMergedList[0]), 6989).
        # uint8 columns: siterank is 4 bits and langid 6 in the posdb
        # key itself, so the old int32 columns shipped 8× the bytes
        # final_multipliers actually needs per doc ---
        sr = np.zeros(self.D_cap, np.uint8)
        dl = np.zeros(self.D_cap, np.uint8)
        if n and dv is None:
            first = np.unique(docidx, return_index=True)[1]
            sr[docidx[first]] = siterank[first]
            dl[docidx[first]] = langid[first]

        # --- dense rows: highest-df terms get a dense [D_cap] impact +
        # runstart row (phase 1 adds them with zero gather/scatter).
        # Built DEVICE-side by one flattened scatter from the doc-pair
        # columns (uploading [V, D] host arrays would ship ~GBs through
        # the host link; the descriptors below are a few KB) ---
        dfs = np.diff(self.dir_dstart)
        tau = max(_env_int("OSSE_DENSE_MIN_DF", DENSE_MIN_DF),
                  self.D_cap // 64)
        # 7 bytes per (term, doc) slot: f16 impact + int32 rs + u8 cnt.
        # The slot count V power-of-two buckets (V is a kernel shape),
        # so the budget must hold for the BUCKETED V — at big D_cap a
        # raw-count budget bucketed up overshot HBM and the int32
        # scatter index space (measured at 250k docs: V 341→512)
        v_cap = 8
        while (2 * v_cap * 7 * self.D_cap <= DENSE_BUDGET_BYTES
               and 2 * v_cap * self.D_cap < (1 << 31)):
            v_cap *= 2
        eligible = np.nonzero(dfs > tau)[0]
        eligible = eligible[np.argsort(-dfs[eligible], kind="stable")]
        dense_terms = eligible[:v_cap]
        V = _bucket(max(len(dense_terms), 1), 8)
        self.dense_slot_of: dict[int, int] = {}
        dr_starts = np.zeros(max(len(dense_terms), 1), np.int32)
        dr_lens = np.zeros(max(len(dense_terms), 1), np.int64)
        for slot, ti in enumerate(dense_terms):
            a, b = int(self.dir_dstart[ti]), int(self.dir_dstart[ti + 1])
            dr_starts[slot] = a
            dr_lens[slot] = b - a
            self.dense_slot_of[int(self.dir_termids[ti])] = slot

        # --- cube rows: the very heaviest terms' [P, D] position cubes,
        # materialized so the full-cube kernel (F2) reads them as plain
        # slices and the FD kernel DMAs their quarter rows. Built
        # device-side by one scatter from the posting columns — no
        # multi-hundred-MB host upload — and kept as quarter rows
        # [Vc·4, P/4, D_cap], the only form the cube has ---
        # adaptive budget: columns + dense rows are obligatory; the
        # cube takes what HBM can spare up to the hard cap
        nb_est = _bucket(max(n, 1), COL_QUANTUM)
        mb_est = _bucket(max(len(doc_col), 1), COL_QUANTUM)
        n2_est = max(_bucket(max(nb_est // 4, min_delta, 1),
                             COL_QUANTUM), COL_QUANTUM)
        cols_bytes = (nb_est + n2_est) * 8 + (mb_est + n2_est) * 11
        dense_bytes = V * self.D_cap * 7
        cube_bytes = min(
            CUBE_BUDGET_BYTES,
            max(1 << 30, HBM_USABLE_BYTES - cols_bytes - dense_bytes
                - WAVE_RESERVE_BYTES))
        # Vc also buckets to a power of two AND its Vc·P·D elements
        # must stay inside int32 for the build's flat scatter — budget
        # against the bucketed size (at 250k docs the raw count 161
        # bucketed to 256 → exactly 2^31 elements → overflow)
        vc_cap = 4
        while (2 * vc_cap * P * self.D_cap * 4 <= cube_bytes
               and 2 * vc_cap * P * self.D_cap < (1 << 31)):
            vc_cap *= 2
        # −1: the last slot stays all-zero — the FD kernel's "absent
        # quarter" target (zero payload = invalid by convention)
        cube_terms = dense_terms[:vc_cap - 1]
        Vc = _bucket(len(cube_terms) + 1, 4)
        self.cube_zero_slot = Vc - 1
        self.cube_slot_of: dict[int, int] = {}
        # per-slot posting-run descriptors only — the scatter targets
        # derive on-device from the resident docc column (docidx<<4 |
        # occ), so neither build path ships posting-sized dst arrays
        c_starts = np.zeros(max(len(cube_terms), 1), np.int32)
        c_lens = np.zeros(max(len(cube_terms), 1), np.int64)
        for slot, ti in enumerate(cube_terms):
            a, b = int(self.dir_pstart[ti]), int(self.dir_pstart[ti + 1])
            c_starts[slot] = a
            c_lens[slot] = b - a
            self.cube_slot_of[int(self.dir_termids[ti])] = slot

        # --- device columns: base + preallocated delta tail ---
        self.h_doc_col = doc_col
        self.Nb = _bucket(max(n, 1), COL_QUANTUM)
        self.Mb = _bucket(max(len(doc_col), 1), COL_QUANTUM)
        # delta tail capacity scales with the base (grown on overflow)
        self.N2 = max(_bucket(max(self.Nb // 4, min_delta, 1),
                              COL_QUANTUM), COL_QUANTUM)
        self.M2 = self.N2
        if dv is not None:
            # device-built columns never left HBM: slice/zero-extend
            # them into the base+delta capacity (rows past dv.n are
            # already zero — the _pad_col convention holds on-device)
            self.d_payload = devbuild.fit(dv.cols["payload"],
                                          self.Nb + self.N2)
            self.d_docc = devbuild.fit(dv.cols["docc"],
                                       self.Nb + self.N2)
            self.d_doc = devbuild.fit(dv.cols["doc_col"],
                                      self.Mb + self.M2)
            self.d_imp = devbuild.fit(dv.cols["imp16"],
                                      self.Mb + self.M2)
            self.d_rs = devbuild.fit(dv.cols["rs"], self.Mb + self.M2)
            self.d_cnt = devbuild.fit(dv.cols["cnt"],
                                      self.Mb + self.M2)
        else:
            self.d_payload = self._put(
                _pad_col(payload, self.Nb + self.N2))
            docc = ((docidx.astype(np.uint32) << _OCC_BITS)
                    | pocc.astype(np.uint32))
            self.d_docc = self._put(_pad_col(docc, self.Nb + self.N2))
            self.d_doc = self._put(_pad_col(doc_col, self.Mb + self.M2))
            # packed resident impacts: the disk cache keeps exact f32
            # (the schema is unchanged); demotion to round-up f16
            # happens at device-put time so HBM holds half the impact
            # bytes while the bounds stay admissible (demote_impacts
            # docstring)
            self.d_imp = self._put(_pad_col(demote_impacts(imp_col),
                                            self.Mb + self.M2))
            self.d_rs = self._put(_pad_col(rs_col, self.Mb + self.M2))
            self.d_cnt = self._put(_pad_col(cnt_col, self.Mb + self.M2))
        dr_cum = np.r_[0, np.cumsum(dr_lens)].astype(np.int32)
        (self.d_dense_imp, self.d_dense_rs,
         self.d_dense_cnt) = _build_dense_rows(
            self.d_doc, self.d_imp, self.d_rs, self.d_cnt,
            self._put(dr_starts), self._put(dr_cum),
            V=V, D=self.D_cap,
            n_lanes=_bucket(max(int(dr_cum[-1]), 1), COL_QUANTUM))
        if dv is not None:
            self.d_siterank, self.d_doclang = devbuild.doc_meta(
                self._put(sr), self._put(dl), dv)
        else:
            self.d_siterank = self._put(sr)
            self.d_doclang = self._put(dl)
        self.d_dead = self._put(np.zeros(self.D_cap, bool))
        self.Vc = Vc
        total = Vc * P * self.D_cap
        c_cum = np.r_[0, np.cumsum(c_lens)].astype(np.int32)
        if len(cube_terms):
            self.d_cube = devbuild._cube_rows(
                self.d_payload, self.d_docc, self._put(c_starts),
                self._put(c_cum), D=self.D_cap, n_positions=P,
                total=total,
                n_lanes=_bucket(max(int(c_cum[-1]), 1), COL_QUANTUM))
        else:
            self.d_cube = jnp.zeros((Vc * 4, P // 4, self.D_cap),
                                    jnp.uint32)
        self._base_fp = fp
        self.full_rebuilds += 1
        # D_cap or a column bucket may have moved: the set is enumerated
        # anew, and its programs are other programs
        self._f1_keys = frozenset(self.f1_programs())
        self._f1_warmed = False
        log.info("device base built: %d postings, %d docs, %d terms "
                 "(%d dense rows, %d cube rows, cap %d)", n, Db,
                 len(self.dir_termids), len(dense_terms),
                 len(cube_terms), self.D_cap)

    def _build_delta(self) -> None:
        """Delta columns from the memtable — O(memtable) per refresh.

        Tombstones (delbit 0) and re-adds mark their base doc dead
        (phase 1 masks base-side bounds, phase 2 masks base run counts)
        and subtract from per-term dfs; positives become delta postings
        + delta doc columns written into the preallocated tails."""
        Db = len(self.base_docids)
        mem = self.coll.posdb.mem.batch()
        self.tomb_df = np.zeros(len(self.dir_termids), np.int64)
        dead = np.zeros(self.D_cap, bool)
        if not len(mem):
            self._set_empty_delta()
            self.d_dead = self._put(dead)
            self.delta_rebuilds += 1
            return
        f = posdb.unpack(mem.keys)
        pos = f["delbit"].astype(bool)

        def base_idx_of(docids_arr):
            di = np.searchsorted(self.base_docids, docids_arr)
            ok = di < Db
            ok[ok] = self.base_docids[di[ok]] == docids_arr[ok]
            return di, ok

        # superseded base docs: explicitly tombstoned OR re-added in the
        # delta (an identical-content re-index annihilates its pairs in
        # the memtable, so the delta positives are the only witness)
        t_di, t_ok = base_idx_of(f["docid"][~pos])
        p_di, p_ok = base_idx_of(f["docid"][pos])
        dead_idx = np.unique(np.concatenate([t_di[t_ok], p_di[p_ok]]))
        dead[dead_idx] = True

        # distinct (term, superseded-doc) pairs → df subtraction (only
        # where the pair actually exists in the base)
        pair_t = np.concatenate([f["termid"][~pos][t_ok],
                                 f["termid"][pos][p_ok]])
        pair_d = np.concatenate([t_di[t_ok], p_di[p_ok]]).astype(np.int64)
        if len(pair_t):
            order = np.lexsort((pair_d, pair_t))
            pair_t, pair_d = pair_t[order], pair_d[order]
            firstp = np.ones(len(pair_t), bool)
            firstp[1:] = (pair_t[1:] != pair_t[:-1]) | \
                (pair_d[1:] != pair_d[:-1])
            pair_t, pair_d = pair_t[firstp], pair_d[firstp]
            ti = np.searchsorted(self.dir_termids, pair_t)
            ok = ti < len(self.dir_termids)
            ok[ok] = self.dir_termids[ti[ok]] == pair_t[ok]
            for term_i in np.unique(ti[ok]):
                m = ok & (ti == term_i)
                a, b = int(self.dir_dstart[term_i]), \
                    int(self.dir_dstart[term_i + 1])
                run = self.h_doc_col[a:b]
                ppos = np.searchsorted(run, pair_d[m])
                inb = ppos < len(run)
                inb[inb] = run[ppos[inb]] == pair_d[m][inb]
                self.tomb_df[term_i] = int(inb.sum())

        # --- positives → delta columns ---
        if pos.any():
            fp_ = {k: v[pos] for k, v in f.items()}
            p_doc = fp_["docid"]
            new_docids = np.unique(p_doc[~p_ok])
            if Db + len(new_docids) > self.D_cap:
                raise _DeltaOverflow(needed_docs=Db + len(new_docids))
            docidx = np.where(
                p_ok, p_di,
                Db + np.searchsorted(new_docids, p_doc)).astype(np.int32)
            dv2 = None
            if devbuild.enabled():
                try:
                    dv2 = devbuild.build_delta(fp_, docidx, self._put)
                except Exception:
                    log.exception("device delta fold failed — falling "
                                  "back to the host pipeline")
                    g_stats.count("build.devbuild_fallback")
                    dv2 = None
            if dv2 is not None:
                n2, m2 = dv2.n, dv2.n_pairs
                if n2 > self.N2 or m2 > self.M2:
                    raise _DeltaOverflow(needed_cols=max(n2, m2))
                if self.Nb + n2 >= _MAX_POSTINGS:
                    raise self._postings_overflow()
                self.dir2_termids = dv2.dir_termids
                self.delta_df = dv2.df
                self.dir2_dstart = dv2.dir_dstart
                self.dir2_pstart = dv2.dir_pstart
                self.all_docids = np.concatenate(
                    [self.base_docids, new_docids])
                # donated in-place rewrites straight from the derive
                # outputs — the fold never round-trips through host
                self.d_payload = _write_tail(
                    self.d_payload,
                    devbuild.fit(dv2.cols["payload"], self.N2),
                    np.int32(self.Nb))
                self.d_docc = _write_tail(
                    self.d_docc,
                    devbuild.fit(dv2.cols["docc"], self.N2),
                    np.int32(self.Nb))
                self.d_doc = _write_tail(
                    self.d_doc,
                    devbuild.fit(dv2.cols["doc_col"], self.M2),
                    np.int32(self.Mb))
                self.d_imp = _write_tail(
                    self.d_imp,
                    devbuild.fit(dv2.cols["imp16"], self.M2),
                    np.int32(self.Mb))
                self.d_rs = _write_tail(
                    self.d_rs,
                    devbuild.offset_runstarts(dv2, self.Nb, self.M2),
                    np.int32(self.Mb))
                self.d_cnt = _write_tail(
                    self.d_cnt,
                    devbuild.fit(dv2.cols["cnt"], self.M2),
                    np.int32(self.Mb))
                self.d_siterank, self.d_doclang = devbuild.doc_meta(
                    self.d_siterank, self.d_doclang, dv2)
                self.d_dead = self._put(dead)
                self.delta_rebuilds += 1
                return
            # delta sort key is (termid, DOC-INDEX, wordpos): new docs'
            # indexes aren't docid-monotonic
            order = np.lexsort((fp_["wordpos"], docidx, fp_["termid"]))
            fp_ = {k: v[order] for k, v in fp_.items()}
            docidx = docidx[order]
            occ = _occ_ranks(fp_["termid"], docidx)
            self.dir2_termids, _, self.delta_df = _term_dfs(
                fp_["termid"], occ == 0)
            keep = occ < self.P
            pocc2 = occ[keep].astype(np.uint8)
            fp_ = {k: v[keep] for k, v in fp_.items()}
            docidx = docidx[keep]
            n2 = len(docidx)
            newpair = np.ones(n2, bool)
            newpair[1:] = (fp_["termid"][1:] != fp_["termid"][:-1]) | \
                (docidx[1:] != docidx[:-1])
            runstart2 = np.nonzero(newpair)[0].astype(np.int64)
            doc2_col = docidx[newpair]
            if n2 > self.N2 or len(doc2_col) > self.M2:
                raise _DeltaOverflow(needed_cols=max(n2, len(doc2_col)))
            if self.Nb + n2 >= _MAX_POSTINGS:
                raise self._postings_overflow()
            count2 = np.diff(np.r_[runstart2, n2])
            imp2 = _impacts_np(fp_, fp_["termid"], docidx, runstart2)
            # runstarts reference the combined column: delta postings
            # live at [Nb, Nb + n2)
            rs2 = (self.Nb + runstart2).astype(np.int32)
            cnt2 = np.minimum(count2, self.P).astype(np.uint8)
            tchange = np.ones(n2, bool)
            tchange[1:] = fp_["termid"][1:] != fp_["termid"][:-1]
            tstarts = np.nonzero(tchange)[0]
            self.dir2_dstart = np.r_[
                np.searchsorted(runstart2, tstarts), len(runstart2)
            ].astype(np.int64)
            self.dir2_pstart = np.r_[tstarts, n2].astype(np.int64)
            self.all_docids = np.concatenate([self.base_docids, new_docids])
            payload2 = pack_payload(fp_)
            # doc-table updates from first delta posting per doc
            first = np.unique(docidx, return_index=True)[1]
            upd_idx = docidx[first].astype(np.int32)
            upd_sr = fp_["siterank"][first].astype(np.uint8)
            upd_dl = fp_["langid"][first].astype(np.uint8)
            # donated in-place rewrites of the delta tails
            self.d_payload = _write_tail(
                self.d_payload,
                self._put(_pad_col(payload2, self.N2)),
                np.int32(self.Nb))
            docc2 = ((docidx.astype(np.uint32) << _OCC_BITS)
                     | pocc2.astype(np.uint32))
            self.d_docc = _write_tail(
                self.d_docc, self._put(_pad_col(docc2, self.N2)),
                np.int32(self.Nb))
            self.d_doc = _write_tail(
                self.d_doc, self._put(_pad_col(doc2_col, self.M2)),
                np.int32(self.Mb))
            self.d_imp = _write_tail(
                self.d_imp,
                self._put(_pad_col(demote_impacts(imp2), self.M2)),
                np.int32(self.Mb))
            self.d_rs = _write_tail(
                self.d_rs, self._put(_pad_col(rs2, self.M2)),
                np.int32(self.Mb))
            self.d_cnt = _write_tail(
                self.d_cnt, self._put(_pad_col(cnt2, self.M2)),
                np.int32(self.Mb))
        else:
            self._set_empty_delta()
            upd_idx = np.empty(0, np.int32)
            upd_sr = upd_dl = upd_idx

        def bpad(a, fill):
            out = np.full(_bucket(max(len(a), 1), DOC_UPD_FLOOR), fill,
                          a.dtype)
            out[: len(a)] = a
            return out
        if len(upd_idx):
            self.d_siterank, self.d_doclang = _apply_doc_meta(
                self.d_siterank, self.d_doclang,
                bpad(upd_idx, upd_idx[0]), bpad(upd_sr, upd_sr[0]),
                bpad(upd_dl, upd_dl[0]))
        self.d_dead = self._put(dead)
        self.delta_rebuilds += 1

    def _set_empty_delta(self) -> None:
        self.dir2_termids = np.empty(0, np.uint64)
        self.dir2_dstart = np.zeros(1, np.int64)
        self.dir2_pstart = np.zeros(1, np.int64)
        self.delta_df = np.empty(0, np.int64)
        self.all_docids = self.base_docids
        # delta tails keep whatever stale content they hold — nothing
        # references it (dir2 is empty), so no device write is needed

    @property
    def n_docs(self) -> int:
        return len(self.all_docids)

    def _column_map(self) -> dict:
        """The resident device columns by name — the HBM ledger's
        (collection, plane, column) unit and the residency-gate byte
        source; extend here when a rebuild path grows a column."""
        return {"payload": self.d_payload, "docc": self.d_docc,
                "doc": self.d_doc, "imp": self.d_imp,
                "rs": self.d_rs, "cnt": self.d_cnt,
                "dense_imp": self.d_dense_imp,
                "dense_rs": self.d_dense_rs,
                "dense_cnt": self.d_dense_cnt, "cube": self.d_cube,
                "siterank": self.d_siterank,
                "doclang": self.d_doclang, "dead": self.d_dead}

    def resident_bytes(self) -> int:
        """Total device bytes this index holds resident — the number
        the background-rebuild double-residency gate reasons about."""
        import numpy as _np
        return sum(int(_np.prod(a.shape)) * a.dtype.itemsize
                   for a in self._column_map().values())

    def _docid_pos(self, docids_arr: np.ndarray) -> tuple[np.ndarray,
                                                          np.ndarray]:
        """(row positions, found mask) of docids in all_docids.
        all_docids = [sorted base] + [sorted delta] — NOT globally
        sorted once a delta exists, so binary search needs the sorted
        view + inverse permutation (rebuilt per refresh)."""
        if getattr(self, "_docid_sorted", None) is None or \
                len(self._docid_order) != len(self.all_docids):
            self._docid_order = np.argsort(self.all_docids,
                                           kind="stable")
            self._docid_sorted = self.all_docids[self._docid_order]
        pos = np.searchsorted(self._docid_sorted, docids_arr)
        ok = pos < len(self._docid_sorted)
        ok[ok] = self._docid_sorted[pos[ok]] == docids_arr[ok]
        rows = np.zeros(len(docids_arr), np.int64)
        rows[ok] = self._docid_order[pos[ok]]
        return rows, ok

    def _cluster_cols(self):
        """Lazily materialized clusterdb columns aligned to all_docids
        (Clusterdb.h:42 — sitehash + langid per docid, dataless)."""
        if getattr(self, "_sitehash", None) is None:
            cl = self.coll.clusterdb.get_all()
            sh = np.zeros(len(self.all_docids), np.int64)
            lg = np.zeros(len(self.all_docids), np.int64)
            if len(cl):
                f = clusterdb_mod.unpack_key(cl.keys)
                rows, ok = self._docid_pos(f["docid"])
                sh[rows[ok]] = f["sitehash"][ok].astype(np.int64)
                lg[rows[ok]] = f["langid"][ok].astype(np.int64)
            self._sitehash = sh
            self._langid_col = lg
        return self._sitehash, self._langid_col

    def sitehash_of(self, docid: int) -> int:
        """Query-time clusterdb read (Clusterdb.h:42 / Msg51.h:96):
        the docid's 26-bit sitehash from the dataless clusterdb records
        — site clustering runs off this column WITHOUT touching titledb
        until the summary stage. Lazily built, aligned to all_docids."""
        sh, _ = self._cluster_cols()
        rows, ok = self._docid_pos(np.array([docid], np.uint64))
        return int(sh[rows[0]]) if ok[0] else 0

    def langid_of(self, docid: int) -> int:
        """Docid → langid from the same clusterdb columns (feeds the
        PostQueryRerank foreign-language demotion without a titlerec
        fetch)."""
        _, lg = self._cluster_cols()
        rows, ok = self._docid_pos(np.array([docid], np.uint64))
        return int(lg[rows[0]]) if ok[0] else 0

    # --- fielddb columns (gbmin/gbmax/gbsortby — the datedb role) -------

    def _field_col(self, fld: str) -> np.ndarray:
        """Dense f64 [n_docs] column for one field aligned to
        all_docids (NaN = doc has no value), cached per Rdb version."""
        cache = getattr(self, "_fcols", None)
        if cache is None:
            cache = self._fcols = {}
        ver = self.coll.fielddb.rdb.version
        hit = cache.get((fld, ver))
        if hit is not None:
            return hit
        docids, vals = self.coll.fielddb.column(fld)
        col = np.full(len(self.all_docids), np.nan)
        if len(docids):
            rows, ok = self._docid_pos(docids)
            col[rows[ok]] = vals[ok]
        if len(cache) > 32:
            cache.clear()
        cache[(fld, ver)] = col
        return col

    def sort_base_of(self, fld: str, desc: bool) -> float | None:
        """This shard's minimum finite sort key for a field (keys are
        v for descending, -v for ascending); None when the shard has
        no finite values (must not poison the cross-shard min)."""
        col = self._field_col(fld)
        key = col if desc else -col
        fin = np.isfinite(key)
        return float(key[fin].min()) if fin.any() else None

    def _filter_sort_cols(self, p: "ResidentPlan"):
        """(d_filter, d_sort, use_filter, use_sort) for one wave —
        device-cached per (spec, fielddb version). The filter is the
        AND of every field's range mask; the sort column is shifted
        positive (matched docs must stay > 0) with missing-field docs
        ranked below every real value."""
        spec = (p.filters, p.sortby, p.sort_base)
        ver = self.coll.fielddb.rdb.version
        cache = getattr(self, "_fswave", None)
        if cache is None:
            cache = self._fswave = {}
        hit = cache.get((spec, ver))
        if hit is not None:
            return hit
        use_filter = bool(p.filters)
        use_sort = p.sortby is not None
        if use_filter:
            mask = np.ones(len(self.all_docids), bool)
            for fld, (lo, hi) in p.filters:
                col = self._field_col(fld)
                with np.errstate(invalid="ignore"):
                    mask &= (col >= lo) & (col <= hi)  # NaN fails both
            fpad = np.zeros(self.D_cap, bool)
            fpad[: len(mask)] = mask
        else:
            fpad = np.zeros(self.D_cap, bool)
        if use_sort:
            fld, desc = p.sortby
            col = self._field_col(fld).copy()
            key = col if desc else -col
            finite = np.isfinite(key)
            key = np.where(finite, key - p.sort_base + 1.0, 0.25)
            spad = np.zeros(self.D_cap, np.float32)
            spad[: len(key)] = key.astype(np.float32)
        else:
            spad = np.zeros(self.D_cap, np.float32)
        out = (self._put(fpad), self._put(spad), use_filter, use_sort)
        if len(cache) > 16:
            cache.clear()
        cache[(spec, ver)] = out
        return out

    # --- planning --------------------------------------------------------

    def _druns_of(self, termid: int):
        """[(is_base, dstart, dlen, dense_slot, cube_slot, pstart, plen)]
        runs for a termid: doc-column run + posting-column run, with the
        dense/cube row slots (-1 when absent)."""
        out = []
        i = int(np.searchsorted(self.dir_termids, np.uint64(termid)))
        if i < len(self.dir_termids) and self.dir_termids[i] == termid:
            a, b = int(self.dir_dstart[i]), int(self.dir_dstart[i + 1])
            if b > a:
                pa, pb = int(self.dir_pstart[i]), int(self.dir_pstart[i + 1])
                out.append((True, a, b - a,
                            self.dense_slot_of.get(termid, -1),
                            self.cube_slot_of.get(termid, -1),
                            pa, pb - pa))
        j = int(np.searchsorted(self.dir2_termids, np.uint64(termid)))
        if j < len(self.dir2_termids) and self.dir2_termids[j] == termid:
            a, b = int(self.dir2_dstart[j]), int(self.dir2_dstart[j + 1])
            if b > a:
                # delta doc/posting columns live past Mb / Nb
                pa, pb = int(self.dir2_pstart[j]), \
                    int(self.dir2_pstart[j + 1])
                out.append((False, self.Mb + a, b - a, -1, -1,
                            self.Nb + pa, pb - pa))
        return out

    @property
    def df_generation(self):
        """The posdb version this resident base was built from — the
        memo key for cluster-wide df caches (``MeshResident._global_df``
        sums ``_df_of`` across every shard; a shard's sum is stable
        until ITS base moves, so the tuple of these across shards keys
        the whole memo)."""
        return self._built_version

    def _df_of(self, termid: int) -> int:
        """Exact document frequency under pending deletes/re-adds:
        base df − superseded-doc pairs + delta df."""
        df = 0
        i = int(np.searchsorted(self.dir_termids, np.uint64(termid)))
        if i < len(self.dir_termids) and self.dir_termids[i] == termid:
            df += int(self.base_df[i]) - int(self.tomb_df[i])
        j = int(np.searchsorted(self.dir2_termids, np.uint64(termid)))
        if j < len(self.dir2_termids) and self.dir2_termids[j] == termid:
            df += int(self.delta_df[j])
        return max(df, 0)

    def plan(self, qplan: QueryPlan, df_of=None,
             total_docs: int | None = None,
             sort_base_of=None) -> ResidentPlan:
        """``df_of``/``total_docs``/``sort_base_of`` override the
        corpus-wide stats: the mesh layer passes CLUSTER-WIDE dfs (and
        the cluster-wide sort-key base for gbsortby) so every shard
        weighs terms identically and cross-shard scores merge
        comparably (the reference ships global termFreqWeights in the
        Msg39 request)."""
        T = _bucket(max(len(qplan.groups), 1), T_FLOOR)
        drows, srows, crows, prows = [], [], [], []
        dfs = np.zeros(max(len(qplan.groups), 1), np.int64)
        matchable = True
        any_required = False
        driver_df = 1 << 60
        groups_have_postings = []
        # direct-cube qualification: per group, the contributing runs
        zq = 4 * getattr(self, "cube_zero_slot", 0)
        g_quarter = np.full((T, 4), zq, np.int32)
        g_qsyn = np.zeros((T, 4), np.uint32)
        direct_ok = True
        for g_i, g in enumerate(qplan.groups):
            subs = g.sublists
            sub_druns = [self._druns_of(s.termid) for s in subs]
            # quota only over sublists with LIVE postings — df under
            # tombstones, matching the host packer's fetched-list mask
            # (a sublist whose every doc was deleted still has base
            # runs in the directory, but its merged host list is empty;
            # diverging masks would give the two paths different slot
            # plans and break parity)
            sub_live_df = [self._df_of(s.termid) for s in subs]
            sp = g.slot_plan(
                self.P,
                present=[bool(d) and ldf > 0
                         for d, ldf in zip(sub_druns, sub_live_df)],
                # LOCAL live dfs for variant funding on both paths: the
                # host packer passes its fetched-list distinct-doc
                # counts, which equal _df_of under tombstones — the
                # funded-variant pick (and so the packed layout) stays
                # bit-identical across host and device planners. The
                # cluster-wide df_of override stays out of this on
                # purpose: it would diverge from what the host path can
                # compute locally.
                df=sub_live_df)
            any_postings = False
            gdf = 0
            g_runs = []
            for s_i, sub in enumerate(subs):
                syn = 1 if sub.kind == SUB_SYNONYM else 0
                base, quota = sp[s_i]
                for is_base, a, ln, dslot, cslot, pa, pl in \
                        sub_druns[s_i]:
                    g_runs.append((is_base, dslot, cslot, syn, base,
                                   quota))
                    # F1 row split: dense [D] impact row vs sparse run.
                    # Sparse runs chunk at LSP_MAX so the lane bucket is
                    # a constant (one compile) and an unbudgeted big
                    # term costs lanes ∝ its real size, not Rs×max
                    if dslot >= 0:
                        drows.append((dslot, g_i, base, quota, syn))
                    else:
                        for off in range(0, ln, LSP_MAX):
                            srows.append((a + off,
                                          min(ln - off, LSP_MAX),
                                          g_i, base, quota, syn,
                                          is_base))
                    # F2 row split: materialized cube slice vs posting
                    # scatter; oversized runs split into several bounded
                    # scatter rows (postings carry their own doc+occ, so
                    # any partition of the range is valid) — every query
                    # is F2-servable and the F1 κ ladder stays ≤ the
                    # routing cut
                    if cslot >= 0:
                        crows.append((cslot, dslot, g_i, base, quota,
                                      syn))
                    else:
                        for off in range(0, pl, F2_SCATTER_MAX):
                            prows.append((pa + off,
                                          min(pl - off, F2_SCATTER_MAX),
                                          g_i, base, quota, syn,
                                          is_base))
                    any_postings = True
                gdf = max(gdf, (df_of or self._df_of)(sub.termid))
            dfs[g_i] = gdf
            groups_have_postings.append(any_postings)
            # direct-cube qualification: cube runs must be base runs at
            # quarter-aligned (base, quota) so the group plane assembles
            # from quarter-row gathers (quarter q of a term's [P, D]
            # cube row holds its occurrences q·P/4..); non-cube runs go
            # to the bounded posting-scatter tail (checked globally
            # below). Misaligned cube runs → generic F2.
            P4 = self.P // 4
            for is_b, dsl, csl, syn, base, quota in g_runs:
                if csl < 0:
                    continue  # scatter-tail run (prows carry it)
                if not (is_b and base % P4 == 0 and quota % P4 == 0
                        and quota > 0 and base + quota <= self.P):
                    direct_ok = False
                    continue
                for k in range(min(quota, self.P - base) // P4):
                    g_quarter[g_i, base // P4 + k] = 4 * csl + k
                    g_qsyn[g_i, base // P4 + k] = syn
            if g.required and not g.negative:
                any_required = True
                driver_df = min(driver_df, gdf)
                if not any_postings:
                    matchable = False
        # direct route needs the scatter tail bounded: big non-cube doc
        # runs (a heavy term outside the cube budget) must assemble
        # through the generic F2
        if (len(prows) > FD_SCATTER_MAX_ROWS
                or sum(p[1] for p in prows) > FD_SCATTER_MAX_LANES):
            direct_ok = False
        # ... and the group bucket capped at 8: the fused-path HBM
        # budget (_fd_bmax) and the [T,P,D] tail cube both size for
        # T ≤ 8; rare wider conjunctions grind through the generic F2
        if len(qplan.groups) > 8:
            direct_ok = False
        if qplan.bool_table is not None:
            # a boolean query is servable iff SOME satisfying presence
            # assignment uses only groups that have postings; the match
            # bound is the union of all groups (any satisfying doc has
            # ≥1 present group — table[0] is False by construction)
            tbl = qplan.bool_table
            bits = np.arange(len(tbl))
            havemask = sum(1 << i for i, h in
                           enumerate(groups_have_postings) if h)
            matchable = bool(tbl[(bits & ~havemask) == 0].any())
            driver_df = int(min(dfs.sum(), self.coll.num_docs or dfs.sum()))
        elif not any_required:
            matchable = False

        required, negative, scored, counts = group_flags(qplan, T)
        freqw = _pad1(
            weights.term_freq_weight(
                dfs[: len(qplan.groups)],
                max(total_docs if total_docs is not None
                    else self.coll.num_docs, 1)), T, 0.5)
        da = np.array(drows, np.int64).reshape(-1, 5)
        sa = np.array(srows, np.int64).reshape(-1, 7)
        ca = np.array(crows, np.int64).reshape(-1, 6)
        pa_ = np.array(prows, np.int64).reshape(-1, 7)
        return ResidentPlan(
            d_slot=da[:, 0].astype(np.int32),
            d_group=da[:, 1].astype(np.int32),
            d_base=da[:, 2].astype(np.int32),
            d_quota=da[:, 3].astype(np.int32),
            d_syn=da[:, 4].astype(np.uint32),
            s_start=sa[:, 0].astype(np.int32),
            s_len=sa[:, 1].astype(np.int32),
            s_group=sa[:, 2].astype(np.int32),
            s_base=sa[:, 3].astype(np.int32),
            s_quota=sa[:, 4].astype(np.int32),
            s_syn=sa[:, 5].astype(np.uint32),
            s_isbase=sa[:, 6].astype(bool),
            c_slot=ca[:, 0].astype(np.int32),
            c_dslot=ca[:, 1].astype(np.int32),
            c_group=ca[:, 2].astype(np.int32),
            c_base=ca[:, 3].astype(np.int32),
            c_quota=ca[:, 4].astype(np.int32),
            c_syn=ca[:, 5].astype(np.uint32),
            p_start=pa_[:, 0].astype(np.int32),
            p_len=pa_[:, 1].astype(np.int32),
            p_group=pa_[:, 2].astype(np.int32),
            p_base=pa_[:, 3].astype(np.int32),
            p_quota=pa_[:, 4].astype(np.int32),
            p_syn=pa_[:, 5].astype(np.uint32),
            p_isbase=pa_[:, 6].astype(bool),
            freq_weight=freqw, required=required, negative=negative,
            scored=scored, counts=counts,
            table=pad_table(qplan.bool_table),
            qlang=qplan.lang, matchable=matchable,
            driver_df=0 if driver_df == 1 << 60 else int(driver_df),
            n_scored=int(np.sum(counts)),
            direct_ok=direct_ok, g_quarter=g_quarter, g_qsyn=g_qsyn,
            has_table=qplan.bool_table is not None,
            filters=tuple(sorted(
                (f, tuple(v)) for f, v in qplan.filters.items())),
            sortby=qplan.sortby,
            sort_base=(
                ((sort_base_of or self.sort_base_of)(*qplan.sortby)
                 or 0.0)
                if qplan.sortby is not None else 0.0))

    # --- execution -------------------------------------------------------

    def search(self, q: str | QueryPlan, topk: int = 64, lang: int = 0):
        """One query → (docids, scores, n_matched)."""
        return self.search_batch([q], topk=topk, lang=lang)[0]

    def search_batch(self, queries, topk: int = 64, lang: int = 0,
                     df_of=None, total_docs: int | None = None,
                     sort_base_of=None):
        """Batched execution: B queries per device round trip. Routing: drivers with a bounded doc set use the
        two-phase pruned kernel (F1); corpus-wide drivers go to the
        full-cube exact kernel (F2) when every sublist fits it.

        One-shot form: issue + collect back-to-back. The resident
        serving loop (query/resident.py) calls the two halves directly
        so batch N+1 dispatches while wave N computes — same code
        either way, so the paths cannot diverge."""
        return self.collect_batch(self.issue_batch(
            queries, topk=topk, lang=lang, df_of=df_of,
            total_docs=total_docs, sort_base_of=sort_base_of))

    def issue_batch(self, queries, topk: int = 64, lang: int = 0,
                    df_of=None, total_docs: int | None = None,
                    sort_base_of=None) -> PendingBatch:
        """Plan + route + dispatch the first round of waves WITHOUT
        fetching anything: every dispatch is async, so this returns as
        soon as the host args are enqueued — no host sync. This is the
        resident loop's steady-state dispatch cost (one enqueue), vs
        the full jit round trip a one-shot ``search_batch`` pays."""
        t_plan, cpu0 = time.perf_counter(), time.thread_time()
        qplans = [q if isinstance(q, QueryPlan) else compile_query(q, lang)
                  for q in queries]
        # plan cache: only the pure-local form is cacheable — mesh calls
        # override dfs/sort bases with cluster-wide values that change
        # per caller and must not leak between planes
        cacheable = (df_of is None and total_docs is None
                     and sort_base_of is None)
        if cacheable:
            plans = []
            # generation captured BEFORE the plan builds: a write
            # landing mid-build moves it, so the entry we store is
            # already dead instead of a pre-write plan served as fresh
            pgen = self._plan_cache.current_gen()
            for qp in qplans:
                ck = (qp.raw, qp.lang)
                hit, p = self._plan_cache.lookup(ck, gen=pgen)
                if not hit:
                    p = self.plan(qp)
                    self._plan_cache.put(ck, p, gen=pgen)
                plans.append(p)
        else:
            plans = [self.plan(qp, df_of=df_of, total_docs=total_docs,
                               sort_base_of=sort_base_of)
                     for qp in qplans]
        trace.record("devindex.plan", t_plan, cpu0=cpu0, queries=len(qplans))
        live = [i for i, p in enumerate(plans) if p.matchable]
        results = [(np.empty(0, np.uint64), np.empty(0, np.float32), 0)
                   ] * len(plans)
        if not live:
            return PendingBatch(plans=plans, results=results, waves=[],
                                k_req=0, k2v=0, f2_nsel=0, bmax=0,
                                topk=topk)
        # corpus-relative routing: a driver matching more than ~1/8th of
        # the corpus (capped at the κ ladder's top rung) prunes badly —
        # full-cube scoring is cheaper than the escalation ladder. With
        # dense impact rows covering mid-df terms, F1 stays cheap up to
        # κ=8192, so only genuinely corpus-wide drivers route to F2
        f2_cut = min(4 * _env_int("OSSE_CUBE_MIN_DF", CUBE_MIN_DF),
                     max(2 * KAPPA_FLOOR, self.n_docs // 8))

        def _route_f2(i):
            p = plans[i]
            if (p.n_scored <= 1 and not p.has_table
                    and len(p.s_start) <= 16):
                # single-scored-group with bounded sparse rows: the
                # phase-1 bound IS the exact single-term score (exact
                # impacts), so F1's top-κ-by-bound is exact ordering at
                # ANY driver df — κ=256 with a 128-wide phase 2 beats
                # full-corpus scoring ~4× per query, and the lossless
                # check still backstops it. The Rs cap keeps the wave
                # inside warmed buckets: a heavy term WITHOUT a dense
                # slot (possible at big shards, where the dense budget
                # caps slots) would otherwise mint an unwarmed
                # Rs=128/256 shape and slow every co-batched lane.
                return False
            if p.driver_df > f2_cut:
                return True
            # heavy multi-group queries that CAN go direct should: the
            # F1 ladder would score a ≥2048-wide phase 2 with loose
            # distance-free bounds (escalation-prone); the direct
            # kernel scores the whole corpus exactly at flat cost and
            # never rungs up
            return (p.direct_ok and p.n_scored > 1
                    and self._kappa_of(p, topk) >= 8 * KAPPA_FLOOR)

        f2 = [i for i in live if _route_f2(i)]
        f1 = [i for i in live if i not in set(f2)]
        fd = [i for i in f2 if plans[i].direct_ok]
        for route, idx in (("f1", f1), ("fd", fd),
                           ("f2", [i for i in f2 if not plans[i].direct_ok])):
            self.route_counts[route] += len(idx)
            if idx:
                g_stats.count(_ROUTE_STAT[route], len(idx))
            wide = sum(1 for i in idx
                       if len(plans[i].required) == WIDE_T)
            if wide:
                g_stats.count(_ROUTE_STAT_WIDE[route], wide)

        # wave loop: issue EVERY sub-batch dispatch, fetch ALL outputs
        # in one device_get (one host sync), then parse; queries whose
        # pruning check failed go into the (rare) next wave with 4x the
        # selection blocks — terminal at D_cap, where selection is
        # complete and the check passes by construction
        # k is bucketed (floor 64, powers of 2) so arbitrary caller topk
        # values don't mint new compile variants; extra rows returned
        # beyond the caller's k are harmless. The KERNEL k2 is pinned to
        # one 128-row value for everyday requests (n ≤ 100 over any s
        # ≤ topk·2 stays under it), so k2 never multiplies the compile
        # grid; only genuinely deep pages mint a bigger variant. k2 is
        # also the phase-2 scoring width (top-k2 by bound), so it sets
        # the dominant gather cost — 128 balances margin vs wave time
        k_req = min(_bucket(max(topk, 1), 64), self.D_cap)
        k2v = min(max(128, k_req), self.D_cap)
        # deep paging (TopTree top-X, X ≫ page): start the F2 selection
        # rung at the requested depth so page-50 doesn't climb a
        # ladder. Big shards start a rung higher: at D ≥ 2^19 the
        # 2048-block selection missed ~2% of queries (each miss reruns
        # a multi-second wave) while the wider top_k costs ~nothing.
        f2_floor = 4096 if self.D_cap >= (1 << 19) else 2048
        f2_nsel = min(max(f2_floor, _bucket(k_req, 2048)), self.D_cap)
        bmax = self._f2_bmax()
        waves = self._issue_waves(plans, f1, f2, topk, k2v, f2_nsel,
                                  bmax)
        return PendingBatch(plans=plans, results=results, waves=waves,
                            k_req=k_req, k2v=k2v, f2_nsel=f2_nsel,
                            bmax=bmax, topk=topk)

    def _issue_waves(self, plans, f1, f2, topk, k2v, f2_nsel, bmax):
        """Build + dispatch one round of waves — all async enqueues;
        the caller fetches every wave's output in ONE device_get."""
        t_issue = time.perf_counter()
        waves = []
        groups: dict[tuple[int, int], list[int]] = {}
        for i in f1:
            kapi = self._kappa_of(plans[i], topk)
            # phase-2 truncation to the top-k2 BY BOUND is only
            # sound-in-practice for single-scored-group plans,
            # where the bound ≈ the exact score; multi-group pair
            # bounds are distance-free (up to ~400× loose), so
            # bound order ≉ exact order and truncation would
            # escalate nearly every query (measured 57%). Multi-
            # group plans score every selected candidate — and on the
            # ladder's rungs single-group plans do too: one width a
            # rung keeps the enumerated program set at one program a
            # (tier, rung), and one-word and several-word queries of a
            # rung ride one wave (κ = 256: twice the 128 phase-2
            # gathers a one-word query would need; PERF.md, PR 30).
            # Above the ladder the truncation is worth its programs.
            if (plans[i].n_scored <= 1
                    and kapi > F1_RUNGS[-1] * KAPPA_FLOOR):
                k2i = min(max(k2v, plans[i].k2_min), kapi)
            else:
                k2i = kapi
            groups.setdefault(
                (kapi, k2i, plans[i].has_table,
                 plans[i].filters, plans[i].sortby), []).append(i)
        for (kappa, k2g, *_spec), idxs in sorted(
                groups.items(), key=lambda kv: str(kv[0])):
            for a in range(0, len(idxs), F1_B):
                chunk = idxs[a:a + F1_B]
                waves.append(("f1", kappa, k2g, chunk,
                              self._run_batch(
                                  [plans[i] for i in chunk],
                                  kappa, k2g)))
        fd = [i for i in f2 if plans[i].direct_ok]
        fg = [i for i in f2 if not plans[i].direct_ok]
        # group FD waves by scatter-tail size: the Lp lane bucket is
        # per-wave, so one heavy-tailed query must not make every
        # lane of its wave pay 16384-lane scatters
        def _lp_of(i):
            p = plans[i]
            ml = int(p.p_len.max()) if len(p.p_len) else 0
            if ml == 0:
                return 0  # pure quarter-row wave: no tail cube
            return 512 if ml <= 512 else (
                F2_LPOST_FLOOR if ml <= F2_LPOST_FLOOR
                else F2_SCATTER_MAX)
        # HARD-partition F2/FD waves by (Lp, filter/sort spec):
        # the filter and sort columns are per-wave kernel args, so
        # a chunk must never mix specs
        spec_of = lambda i: (plans[i].filters, plans[i].sortby,
                             plans[i].has_table)
        fd_parts: dict = {}
        for i in fd:
            fd_parts.setdefault((_lp_of(i), spec_of(i)),
                                []).append(i)
        fd_step = self._fd_bmax()
        for _, idxs in sorted(fd_parts.items(),
                              key=lambda kv: str(kv[0])):
            for a in range(0, len(idxs), fd_step):
                chunk = idxs[a:a + fd_step]
                waves.append(("f2", 0, k2v, chunk,
                              self._run_batch_fd(
                                  [plans[i] for i in chunk],
                                  k2v, f2_nsel)))
        fg_parts: dict = {}
        for i in fg:
            fg_parts.setdefault(spec_of(i), []).append(i)
        for _, idxs in sorted(fg_parts.items(),
                              key=lambda kv: str(kv[0])):
            for a in range(0, len(idxs), bmax):
                chunk = idxs[a:a + bmax]
                waves.append(("f2", 0, k2v, chunk,
                              self._run_batch_f2(
                                  [plans[i] for i in chunk],
                                  k2v, f2_nsel)))
        trace.record("devindex.issue", t_issue, waves=len(waves))
        return waves

    def collect_batch(self, pending: PendingBatch):
        """Fetch + parse every issued wave, re-issuing the (rare)
        escalation rungs inline until all queries emit — the ONE
        ``device_get`` per round is the only host sync on the path."""
        plans, results = pending.plans, pending.results
        waves, f2_nsel = pending.waves, pending.f2_nsel
        k_req = pending.k_req
        while waves:
            t_fetch = time.perf_counter()
            outs = jax.device_get([w[4] for w in waves])
            t_got = time.perf_counter()
            kinds = "+".join(sorted({w[0] for w in waves}))
            stat = _WAVE_STAT.get((kinds, _wave_nbucket(len(waves))))
            if stat is not None:
                trace.record(stat, t_fetch, t_got)
            for w in waves:
                if w[0] == "f1":
                    trace.record(_WAVE_KAPPA_STAT.get(
                        w[1], _WAVE_KAPPA_STAT["max"]), t_fetch, t_got)
            fetched = int(sum(np.asarray(o).nbytes for o in outs))
            # device-time attribution: device_get blocks until every
            # issued wave completes (the block_until_ready delta), so
            # this interval IS the device time of the round, and the
            # fetched buffers are the bytes moved device→host
            trace.record(
                "devindex.device", t_fetch, t_got,
                kinds=kinds, waves=len(waves), bytes=fetched)
            f1_next: list[int] = []
            f2_next: list[int] = []
            for (kind, kappa, k2g, idxs, _), out in zip(waves, outs):
                for row, i in zip(out, idxs):
                    k2p = min(k2g, f2_nsel, self.D_cap) if kind == "f2" \
                        else k2g
                    nm, missed, idx, scores = self._parse_out(row, k2p)
                    kth = float(scores[k_req - 1]) if (
                        k2p >= k_req and scores[k_req - 1] > 0.0) else 0.0
                    if missed > kth * _TIE_TOL:
                        if kind == "f1" and (kappa < self.D_cap
                                             or k2p < self.D_cap):
                            # pruning miss — widen the κ rung AND the
                            # phase-2 width, rerun; terminal at
                            # κ = k2 = D_cap where scoring is complete
                            # and missed is exactly 0
                            plans[i].kappa_min = min(4 * kappa,
                                                     self.D_cap)
                            plans[i].k2_min = min(
                                4 * max(k2p, KAPPA_FLOOR // 2),
                                self.D_cap)
                            f1_next.append(i)
                            continue
                        if kind == "f2" and f2_nsel < self.D_cap:
                            f2_next.append(i)
                            continue
                    if devcheck.enabled():
                        # guardrail sweep on every emitted wave row:
                        # finite, sorted, in-bounds (devcheck docs);
                        # apply_fault is the test-only injector
                        idx, scores = devcheck.apply_fault(
                            idx, scores, self.n_docs)
                        devcheck.check_topk(scores, idx, self.n_docs,
                                            route=kind)
                    self._emit(results, i, nm, idx, scores)
            if f1_next or f2_next:
                self.escalations += len(f1_next) + len(f2_next)
            if devwatch.enabled():
                # flight-recorder round detail: measured device time +
                # fetched bytes next to the modeled F1 wave bytes, so
                # the /admin/device waterfall shows model vs reality
                devwatch.note_round(
                    coll=self.coll.name, kinds=kinds,
                    waves=len(waves), device_s=t_got - t_fetch,
                    bytes_out=fetched,
                    modeled_f1_bytes=int(sum(
                        self.wave_bytes_per_query(
                            [plans[i] for i in w[3]]) * len(w[3])
                        for w in waves if w[0] == "f1")),
                    escalations=len(f1_next) + len(f2_next))
            f2_nsel = min(f2_nsel * 4, self.D_cap)
            waves = self._issue_waves(
                plans, f1_next, f2_next, pending.topk, pending.k2v,
                f2_nsel, pending.bmax) if (f1_next or f2_next) else []
        return results

    def f1_programs(self) -> list[tuple]:
        """The closed F1 program space (``F1_TIERS``' invariant), as
        ``_costed`` buckets with no table, filter or sort — enumerated
        from D_cap alone, no query seen. At T = T_FLOOR, (B, Rd, Rs,
        Lsp, κ, k2): first rung, every tier; the two rungs above it,
        the long-run tiers. Then at T = WIDE_T, (B, Rd, Rs, Lsp, κ,
        k2, T): the top tier on each rung. ``warm_f1`` walks each
        family in this order."""
        kap = [min(r * KAPPA_FLOOR, self.D_cap) for r in F1_RUNGS]
        out = [(F1_B, *tier, kap[0], kap[0]) for tier in F1_TIERS]
        for kappa in kap[1:]:
            out += [(F1_B, *tier, kappa, kappa) for tier in F1_UPPER_TIERS]
        out += [(F1_B, *F1_TIERS[-1], kappa, kappa, WIDE_T)
                for kappa in kap]
        return list(dict.fromkeys(out))   # a small D_cap folds rungs

    def warm_f1(self) -> int:
        """Dispatch every program of ``f1_programs()`` once (a dummy
        wave each, results discarded), so that no F1 query of the set
        can meet a compile afterwards. The served path calls this at
        start-up, after the base is built and before the index is
        handed to the resident loop (``SearchHTTPServer._warm_device``
        -> ``ResidencyManager.loop_for(warm=True)``); a second call on
        a warmed index returns at once. Compiles persist in the XLA
        compilation cache
        (utils/compilecache.py): cold it is seconds a program, after a
        restart a load. FD and F2 programs are not part of it (a fused
        FD variant compiles for 90 s: ROADMAP S1). Every program is
        traced in the set's own order, one after another (a program's
        cache key can depend on which program of its family was traced
        first: PERF.md, Findings), then the compiles or cache
        loads run side by side, then each program is dispatched."""
        if self._f1_warmed:
            return 0
        z = np.zeros

        def dummy(T: int, nd: int, ns: int, run: int) -> ResidentPlan:
            req = z(T, bool)
            req[0] = True
            one = lambda n, dt=np.int32: np.ones(n, dt)
            return ResidentPlan(
                d_slot=z(nd, np.int32), d_group=z(nd, np.int32),
                d_base=z(nd, np.int32), d_quota=one(nd),
                d_syn=z(nd, np.uint32),
                s_start=z(ns, np.int32), s_len=np.full(ns, run, np.int32),
                s_group=z(ns, np.int32), s_base=z(ns, np.int32),
                s_quota=one(ns), s_syn=z(ns, np.uint32),
                s_isbase=one(ns, bool),
                c_slot=z(1, np.int32), c_dslot=z(1, np.int32),
                c_group=z(1, np.int32), c_base=z(1, np.int32),
                c_quota=one(1), c_syn=z(1, np.uint32),
                p_start=z(1, np.int32), p_len=one(1),
                p_group=z(1, np.int32), p_base=z(1, np.int32),
                p_quota=one(1), p_syn=z(1, np.uint32),
                p_isbase=one(1, bool),
                freq_weight=np.full(T, 0.5, np.float32),
                required=req, negative=z(T, bool), scored=req.copy(),
                counts=req.copy(), table=pad_table(None), qlang=0,
                matchable=True)

        with trace.timed_span("devindex.warm_f1"):
            keys = self.f1_programs()
            calls = [self._f1_call([dummy(t[0] if t else T_FLOOR, rd, rs,
                                          lsp)], kappa, k2)
                     for _, rd, rs, lsp, kappa, k2, *t in keys]
            lowered = [_two_phase.lower(*args, **statics)
                       for _, _, args, statics in calls]
            with ThreadPoolExecutor(4, "warm-f1") as ex:
                list(ex.map(lambda lo: lo.compile(), lowered))
            jax.device_get([self._costed("devindex._two_phase", bucket,
                                         modeled, _two_phase, *args,
                                         **statics)
                            for bucket, modeled, args, statics in calls])
        g_stats.count("devindex.f1.programs_enumerated", len(keys))
        self._f1_warmed = True
        return len(keys)

    def warm_plans(self) -> None:
        """Build-time pre-warm of the host lazies the FIRST query would
        otherwise pay (the cold-plan spike: ``devindex.plan`` max
        1168 ms vs 0.3 ms min): the docid argsort + inverse permutation
        and the clusterdb sitehash/langid columns, a few ms. The F1
        program set is :meth:`warm_f1`'s, which the served path calls
        at start-up."""
        self._docid_pos(np.empty(0, np.uint64))
        self._cluster_cols()

    def _parse_out(self, row, k2: int):
        nm = int(row[0])
        missed = float(np.asarray(row[1:2]).view(np.float32)[0])
        idx = row[2:2 + k2].astype(np.int64)
        scores = np.asarray(row[2 + k2:]).view(np.float32)
        return nm, missed, idx, scores

    def _emit(self, results, i, nm, idx, scores):
        keep = scores > 0.0
        results[i] = (
            self.all_docids[np.clip(idx[keep], 0,
                                    max(self.n_docs - 1, 0))],
            scores[keep], nm)

    def _kappa_of(self, p: ResidentPlan, topk: int) -> int:
        """κ rung for a plan.

        Single-scored-group queries get a SPECULATIVE small κ even when
        the driver matches far more docs: with one group the phase-1
        bound is the impact itself — nearly the exact score — so the
        top-κ-by-bound almost always contains the top-k exact and the
        lossless missed-vs-kth check just passes (escalation covers the
        rare miss). Phase-2 gather cost is ∝ κ·T·P, so this is the
        difference between ~9 ms and ~70 ms for a hot single-term
        query. Multi-group queries rung by driver_df as before: their
        pair bounds are distance-free (loose), and a small κ would
        escalate every time."""
        if p.n_scored <= 1:
            # top-MAX_TOP-cut impacts make the single-group bound the
            # exact score (mod float association): the smallest rung
            # suffices and phase-2 cost collapses to κ=256 gathers
            need = max(KAPPA_FLOOR, 2 * topk, p.kappa_min)
        else:
            need = max(KAPPA_FLOOR, 2 * topk, p.driver_df, p.kappa_min)
        for r in F1_RUNGS:
            if need <= r * KAPPA_FLOOR:
                return min(r * KAPPA_FLOOR, self.D_cap)
        return min(_bucket(need, KAPPA_FLOOR), self.D_cap)

    def _f2_bmax(self) -> int:
        """F2 batch cap: full-cube intermediates are ~48 bytes/doc/query
        ([T,P,D] cube+validity+scores) — bound them to ~1.5 GB."""
        per_q = 48 * MAX_POSITIONS * self.D_cap
        return max(4, min(16, (1536 << 20) // max(per_q, 1)))

    def _fd_bmax(self) -> int:
        """FD batch cap. The fused Pallas route never materializes the
        per-query cube — its only [T,P,D]-scale HBM is the posting-tail
        scatter target — so it batches ~4× deeper than the generic F2
        envelope at big D (T ≤ 8 worst case)."""
        from .pallas_scores import use_fused
        if use_fused(self.D_cap):
            per_q = 8 * MAX_POSITIONS * self.D_cap * 4
            return max(4, min(16, (4 << 30) // max(per_q, 1)))
        return max(4, min(16, self._f2_bmax()))

    def wave_bytes_per_query(self, plans: list[ResidentPlan]) -> float:
        """Modelled HBM bytes the F1 wave path streams per query under
        the packed layout (f16 impacts, uint8 doc meta, length-bucketed
        Lsp tiles): what devwatch shows beside a round's measured
        device time. Shares _run_batch's tier chain so the model moves
        when the layout does; the per-plan Lsp tile is the fine-grained
        bound (real waves pay their rung-group's max)."""
        imp = 2
        meta = 1
        V = self.d_dense_imp.shape[0]
        D = self.D_cap
        B = max(len(plans), 1)
        total = 0.0
        for p in plans:
            mls = int(p.s_len.max()) if len(p.s_len) else 0
            T = max(len(p.required), 1)
            Rd, Rs, Lsp = _f1_rows(max(len(p.d_slot), 1),
                                   max(len(p.s_start), 1), mls,
                                   wide=T == WIDE_T)
            k2 = min(128, D)
            # sparse lane gathers: doc4 + imp + rs4 + cnt1 + dead1
            total += Rs * Lsp * (4 + imp + 4 + 1 + 1)
            # doc-meta columns the multiplier/alive gates stream [D]
            total += D * (meta + meta + 1)
            # phase-2 payload + dense rs/cnt gathers (layout-invariant)
            total += k2 * T * self.P * 4 + Rd * k2 * 5
        # the [V, D] dense impact matrix streams once per WAVE
        total += V * D * imp
        return total / B

    def _costed(self, name: str, bucket: tuple, modeled_bytes,
                fn, *args, **statics):
        """Dispatch a jitted kernel, roofline-attributing its
        (kernel, shape-bucket) on first sight: devwatch pulls
        flops/bytes from ``lower().compile().cost_analysis()`` once
        per bucket (a dict hit afterwards), so every warmed shape has
        a bandwidth/compute verdict next to the modeled wave bytes.
        Every dispatch is counted by (program, shape bucket) in
        ``self.dispatches``: which program each wave rode is the
        index's own record (``/admin/device``), devwatch on or off;
        and in its slot's counter (``_PROGRAM_SLOT_STAT``), so that a
        reader of g_stats can tell how many programs a window rode."""
        key = (name, tuple(int(x) for x in bucket))
        n = self.dispatches.get(key, 0)
        self.dispatches[key] = n + 1
        if n == 0:
            self._slot_of[key] = _PROGRAM_SLOT_STAT[
                min(len(self._slot_of), len(_PROGRAM_SLOT_STAT) - 1)]
        g_stats.count(self._slot_of[key])
        if devwatch.enabled():
            devwatch.note_cost(
                name, bucket,
                lambda: fn.lower(*args, **statics).compile(),
                modeled_bytes=modeled_bytes)
        return fn(*args, **statics)

    def _run_batch(self, plans: list[ResidentPlan], kappa: int, k2: int):
        bucket, modeled, args, statics = self._f1_call(plans, kappa, k2)
        # lanes from len(plans) on are padding: ``_two_phase`` is told
        # how many are live and runs none of its per-lane work for them
        g_stats.count("devindex.f1.lanes", bucket[0])
        g_stats.count("devindex.f1.pad_lanes", bucket[0] - len(plans))
        return self._costed("devindex._two_phase", bucket, modeled,
                            _two_phase, *args, **statics)

    def _f1_call(self, plans: list[ResidentPlan], kappa: int, k2: int):
        """An F1 wave's (program key, modelled bytes, arguments,
        statics) for ``_two_phase``."""
        # the row shape is a TIER of a chain (F1_TIERS): the wave pays
        # for its widest rider — dense rows, sparse rows, and the lane
        # tile of its longest sparse run (runs chunk at LSP_MAX in the
        # planner, so the top tile always fits) — rounded up to the
        # first tier that covers all three, so whichever riders meet in
        # a wave the shape is one the index enumerated (f1_programs)
        mrd = max([len(p.d_slot) for p in plans] + [1])
        mrs = max([len(p.s_start) for p in plans] + [1])
        mls = max([int(p.s_len.max()) if len(p.s_len) else 0
                   for p in plans] + [0])
        T = max(len(p.required) for p in plans)
        Rd, Rs, Lsp = _f1_rows(mrd, mrs, mls, kappa > KAPPA_FLOOR,
                               wide=T == WIDE_T)
        # one B: a wider bucket is a program of its own to compile
        # (``_issue_waves`` chunks at B); the per-lane work (phase-1
        # chains, phase-2 gathers) runs for the live lanes only
        B = F1_B
        if len(plans) > B:  # stray caller overshoot: correctness first
            B = _bucket(len(plans), 4)
        use_table = any(p.has_table for p in plans)
        d_filter, d_sort, uf, us = self._filter_sort_cols(plans[0])
        # T is in the key of every program but the T_FLOOR family's
        # (whose keys read as they always have)
        bucket = (B, Rd, Rs, Lsp, kappa, k2) + (
            () if T == T_FLOOR else (T,))
        if use_table or uf or us or bucket not in self._f1_keys:
            g_stats.count("devindex.f1.key_outside_set")

        def pad_plan(p: ResidentPlan | None):
            if p is None:
                return (np.full(Rd, -1, np.int32), np.zeros(Rd, np.int32),
                        np.zeros(Rd, np.int32), np.ones(Rd, np.int32),
                        np.zeros(Rd, np.uint32),
                        np.zeros(Rs, np.int32), np.zeros(Rs, np.int32),
                        np.zeros(Rs, np.int32), np.zeros(Rs, np.int32),
                        np.ones(Rs, np.int32), np.zeros(Rs, np.uint32),
                        np.ones(Rs, bool),
                        np.full(T, 0.5, np.float32), np.zeros(T, bool),
                        np.zeros(T, bool), np.zeros(T, bool),
                        np.zeros(T, bool), np.ones(TABLE_SIZE, bool),
                        np.int32(0))
            pr = lambda a, n, fill: _pad1(a, n, fill)
            return (pr(p.d_slot, Rd, -1), pr(p.d_group, Rd, 0),
                    pr(p.d_base, Rd, 0), pr(p.d_quota, Rd, 1),
                    pr(p.d_syn, Rd, 0),
                    pr(p.s_start, Rs, 0), pr(p.s_len, Rs, 0),
                    pr(p.s_group, Rs, 0), pr(p.s_base, Rs, 0),
                    pr(p.s_quota, Rs, 1), pr(p.s_syn, Rs, 0),
                    pr(p.s_isbase, Rs, True),
                    _pad1(p.freq_weight, T, 0.5),
                    _pad1(p.required, T, False),
                    _pad1(p.negative, T, False),
                    _pad1(p.scored, T, False),
                    _pad1(p.counts, T, False), p.table,
                    np.int32(p.qlang))

        padded = [pad_plan(p) for p in plans] \
            + [pad_plan(None)] * (B - len(plans))
        args = [np.stack([p[j] for p in padded]) for j in range(19)]
        # few-hot selector for the phase-1 dense matmul: one 1.0 per
        # dense row occurrence at (query, group, dense slot)
        V = self.d_dense_imp.shape[0]
        sel = np.zeros((B, T, V), np.float32)
        for b, p in enumerate(plans):
            for slot, g in zip(p.d_slot, p.d_group):
                if slot >= 0:
                    sel[b, g, slot] += 1.0
        log.debug("f1 wave: B=%d Rd=%d Rs=%d Lsp=%d kappa=%d k2=%d",
                  B, Rd, Rs, Lsp, kappa, k2)
        # host args ride the (async) dispatch; returned WITHOUT fetching
        # — the caller fetches every wave's output in ONE device_get
        # (each separate blocking fetch is a host sync of its own)
        modeled = self.wave_bytes_per_query(plans) * len(plans) \
            if devwatch.enabled() else None
        return bucket, modeled, (
            self.d_payload, self.d_doc, self.d_imp, self.d_rs,
            self.d_cnt, self.d_dense_imp, self.d_dense_rs,
            self.d_dense_cnt,
            self.d_siterank, self.d_doclang, self.d_dead,
            np.int32(self.n_docs), d_filter, d_sort, sel,
            np.int32(len(plans)), *args), dict(
            n_positions=self.P, lsp=Lsp, kappa=kappa, k2=k2,
            use_table=use_table, use_filter=uf, use_sort=us)

    def _run_batch_f2(self, plans: list[ResidentPlan], k2: int,
                      n_sel: int):
        Rc = _bucket(max([len(p.c_slot) for p in plans] + [1]), 8)
        mrp = max([len(p.p_start) for p in plans] + [1])
        Rp = 8 if mrp <= 8 else (32 if mrp <= 32 else _bucket(mrp, 64))
        maxlen = max([int(p.p_len.max()) if len(p.p_len) else 1
                      for p in plans] + [1])
        Lp = F2_LPOST_FLOOR if maxlen <= F2_LPOST_FLOOR else F2_SCATTER_MAX
        T = max(len(p.required) for p in plans)
        # two B buckets: the latency path (≤4 real queries) must not
        # pay a full B=bmax wave of [T, P, D] work for its pad lanes
        B = 4 if len(plans) <= 4 else max(self._f2_bmax(), len(plans))

        def pad_plan(p: ResidentPlan | None):
            if p is None:
                return (np.full(Rc, -1, np.int32), np.zeros(Rc, np.int32),
                        np.zeros(Rc, np.int32), np.zeros(Rc, np.int32),
                        np.ones(Rc, np.int32), np.zeros(Rc, np.uint32),
                        np.zeros(Rp, np.int32), np.zeros(Rp, np.int32),
                        np.zeros(Rp, np.int32), np.zeros(Rp, np.int32),
                        np.ones(Rp, np.int32), np.zeros(Rp, np.uint32),
                        np.ones(Rp, bool),
                        np.full(T, 0.5, np.float32), np.zeros(T, bool),
                        np.zeros(T, bool), np.zeros(T, bool),
                        np.zeros(T, bool), np.ones(TABLE_SIZE, bool),
                        np.int32(0))
            pr = lambda a, n, fill: _pad1(a, n, fill)
            return (pr(p.c_slot, Rc, -1), pr(p.c_dslot, Rc, 0),
                    pr(p.c_group, Rc, 0), pr(p.c_base, Rc, 0),
                    pr(p.c_quota, Rc, 1), pr(p.c_syn, Rc, 0),
                    pr(p.p_start, Rp, 0), pr(p.p_len, Rp, 0),
                    pr(p.p_group, Rp, 0), pr(p.p_base, Rp, 0),
                    pr(p.p_quota, Rp, 1), pr(p.p_syn, Rp, 0),
                    pr(p.p_isbase, Rp, True),
                    _pad1(p.freq_weight, T, 0.5),
                    _pad1(p.required, T, False),
                    _pad1(p.negative, T, False),
                    _pad1(p.scored, T, False),
                    _pad1(p.counts, T, False), p.table,
                    np.int32(p.qlang))

        padded = [pad_plan(p) for p in plans] \
            + [pad_plan(None)] * (B - len(plans))
        args = [np.stack([p[j] for p in padded]) for j in range(20)]
        log.debug("f2 wave: B=%d Rc=%d Rp=%d Lp=%d k2=%d n_sel=%d",
                  B, Rc, Rp, Lp, k2, n_sel)
        d_filter, d_sort, uf, us = self._filter_sort_cols(plans[0])
        return self._costed(
            "devindex._full_cube",
            (B, Rc, Rp, Lp, k2, min(n_sel, self.D_cap)),
            None, _full_cube,
            self.d_payload, self.d_docc, self.d_cube,
            self.d_dense_cnt, self.d_siterank, self.d_doclang,
            self.d_dead, np.int32(self.n_docs), d_filter, d_sort,
            *args,
            n_positions=self.P, lpost=Lp, k2=k2,
            n_sel=min(n_sel, self.D_cap),
            use_table=any(p.has_table for p in plans),
            use_filter=uf, use_sort=us)

    def _run_batch_fd(self, plans: list[ResidentPlan], k2: int,
                      n_sel: int):
        """Direct-cube (FD) wave: heavy sublists read as quarter-rows
        of the resident cube, small ones ride a bounded scatter tail —
        no per-query cube assembly."""
        T = max(len(p.required) for p in plans)
        B = 4 if len(plans) <= 4 else max(self._fd_bmax(), len(plans))
        # lanes from len(plans) on are padding: the fused kernel is told
        # how many are live and spends nothing on the rest
        g_stats.count("devindex.fd.lanes", B)
        g_stats.count("devindex.fd.pad_lanes", B - len(plans))
        zq = 4 * getattr(self, "cube_zero_slot", 0)
        cs = np.full((B, T, 4), zq, np.int32)
        sy = np.zeros((B, T, 4), np.uint32)
        for b, p in enumerate(plans):
            cs[b, : len(p.g_quarter)] = p.g_quarter
            sy[b, : len(p.g_qsyn)] = p.g_qsyn
        mrp = max([len(p.p_start) for p in plans] + [1])
        Rp = 4 if mrp <= 4 else _bucket(mrp, 8)
        maxlen = max([int(p.p_len.max()) if len(p.p_len) else 0
                      for p in plans] + [0])
        # Lp = 0: every query in the wave is pure quarter-rows — the
        # fused kernel then compiles without a tail input at all
        Lp = 0 if maxlen == 0 else (512 if maxlen <= 512 else (
            F2_LPOST_FLOOR if maxlen <= F2_LPOST_FLOOR
            else F2_SCATTER_MAX))
        if Lp and T == WIDE_T:
            # live lanes whose kernel reads a tail cube beside the rows
            g_stats.count("devindex.fd.t8_tail", len(plans))

        def pad_plan(p: ResidentPlan | None):
            if p is None:
                return (np.zeros(Rp, np.int32), np.zeros(Rp, np.int32),
                        np.zeros(Rp, np.int32), np.zeros(Rp, np.int32),
                        np.ones(Rp, np.int32), np.zeros(Rp, np.uint32),
                        np.ones(Rp, bool),
                        np.full(T, 0.5, np.float32), np.zeros(T, bool),
                        np.zeros(T, bool), np.zeros(T, bool),
                        np.zeros(T, bool), np.ones(TABLE_SIZE, bool),
                        np.int32(0))
            pr = lambda a, n, fill: _pad1(a, n, fill)
            return (pr(p.p_start, Rp, 0), pr(p.p_len, Rp, 0),
                    pr(p.p_group, Rp, 0), pr(p.p_base, Rp, 0),
                    pr(p.p_quota, Rp, 1), pr(p.p_syn, Rp, 0),
                    pr(p.p_isbase, Rp, True),
                    _pad1(p.freq_weight, T, 0.5),
                    _pad1(p.required, T, False),
                    _pad1(p.negative, T, False),
                    _pad1(p.scored, T, False),
                    _pad1(p.counts, T, False), p.table,
                    np.int32(p.qlang))

        padded = [pad_plan(p) for p in plans] \
            + [pad_plan(None)] * (B - len(plans))
        args = [np.stack([p[j] for p in padded]) for j in range(14)]
        log.debug("fd wave: B=%d T=%d Rp=%d Lp=%d k2=%d n_sel=%d",
                  B, T, Rp, Lp, k2, n_sel)
        d_filter, d_sort, uf, us = self._filter_sort_cols(plans[0])
        d_cube = self.d_cube
        if devcheck.enabled():
            # guardrail sweep over the resident position cube before
            # the wave reads it: nonzero payloads must decode to a
            # legal hashgroup (a corrupt/torn tile fails this with
            # probability 5/16 per word). Host-side, pre-dispatch —
            # _direct_cube itself is jitted so checkify can't run there
            d_cube = devcheck.apply_cube_fault(d_cube)
            devcheck.check_cube(d_cube, route="fd")
        return self._costed(
            "devindex._direct_cube",
            (B, T, Rp, Lp, k2, min(n_sel, self.D_cap)),
            None, _direct_cube,
            d_cube, self.d_payload, self.d_docc,
            self.d_siterank, self.d_doclang, self.d_dead,
            np.int32(self.n_docs), d_filter, d_sort, cs, sy,
            np.array([len(plans)], np.int32), *args,
            n_positions=self.P, lpost=Lp, k2=k2,
            n_sel=min(n_sel, self.D_cap),
            use_table=any(p.has_table for p in plans),
            use_filter=uf, use_sort=us)


@jax.jit
def _apply_doc_meta(sr, dl, idx, vsr, vdl):
    return sr.at[idx].set(vsr), dl.at[idx].set(vdl)


@partial(jax.jit, static_argnames=("n_positions", "lsp", "kappa", "k2",
                                   "use_table", "use_filter",
                                   "use_sort"))
def _two_phase(d_payload, d_doc, d_imp, d_rs, d_cnt,
               d_dense_imp, d_dense_rs, d_dense_cnt,
               d_siterank, d_doclang, d_dead, n_docs_total,
               d_filter, d_sort, d_sel, n_live,
               d_slot, d_group, d_base, d_quota, d_syn,
               s_start, s_len, s_group, s_base, s_quota, s_syn, s_isbase,
               freqw, required, negative, scored, counts, table, qlang,
               n_positions: int, lsp: int, kappa: int, k2: int,
               use_table: bool = True, use_filter: bool = False,
               use_sort: bool = False):
    """The fused two-phase kernel, one query lane after another.

    Phase 1 = dense upper bounds + intersection + approx top-κ (the
    maxPossibleScore prune, Posdb.cpp:6052); phase 2 = exact cube scoring
    of the κ candidates (docIdLoop semantics via scorer.min_scores).
    Output per query: [n_matched, bitcast(max missed bound), κ-top-k2
    doc indices, bitcast(exact scores)]. ``n_live`` (int32, traced)
    counts the wave's live lanes, which come first: the lanes from it on
    are padding, run nothing past the batched phase-1 matmul, and read
    as rows of zeros."""
    D = d_dead.shape[0]
    V = d_dense_imp.shape[0]
    M = d_doc.shape[0]
    N = d_payload.shape[0]
    P = n_positions
    big = jnp.float32(9.99e8)

    # ---- phase 1 dense accumulation as ONE matmul on the MXU:
    # ubb[b, t, :] = Σ_v sel[b, t, v] · dense_imp[v, :]. The selector
    # [B·T, V] is a few-hot host-built matrix; the whole batch reads
    # the [V, D] impact matrix ONCE at bandwidth speed. The former
    # per-row dynamic slices cost ~91 ms/wave at B=32 (per-lane row
    # copies); this is ~1 ms. The impact matrix is packed f16 at
    # 1/IMPACT_SCALE (round-up demoted, so scaled-back values stay ≥
    # the exact f32 impact); the selector's small integer counts are
    # f16-exact, and the product accumulates in f32
    # (preferred_element_type) — the bound stays admissible and the
    # in-kernel ×1.00001 inflation covers the f32 accumulation
    # reassociation as before. The exponent shift is undone exactly
    # (power of two) on the f32 result.
    B, Ts, _ = d_sel.shape
    with jax.named_scope("f1.phase1_dense_bounds"):
        ubb_mm = jax.lax.dot_general(
            d_sel.reshape(B * Ts, V).astype(d_dense_imp.dtype),
            d_dense_imp,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).reshape(B, Ts, D) \
            * jnp.float32(IMPACT_SCALE)

    def one(ubb, d_slot, d_group, d_base, d_quota, d_syn,
            s_start, s_len, s_group, s_base, s_quota, s_syn, s_isbase,
            freqw, required, negative, scored, counts, table, qlang):
        T = required.shape[0]
        Rd = d_slot.shape[0]
        Rs = s_start.shape[0]
        t_ax = jnp.arange(T)
        live = ~d_dead                                        # [D]

        with jax.named_scope("f1.phase1_bounds"):
            # ---- phase 1: group upper bounds over the full doc axis
            # (dense-row part arrives precomputed from the batch matmul) ----
            dgate = (d_slot >= 0)
            # sparse rows: one fused contiguous gather + bounded scatter-add
            # into [T, D]. Base-row lanes of dead docs zero at GATHER time
            # (a [Rs, Lsp] gather of the dead vector) so base and delta
            # share one scatter target — half the [2, T, D] footprint the
            # former base/delta target split paid per lane
            lane = jnp.arange(lsp, dtype=jnp.int32)
            sidx = s_start[:, None] + lane[None, :]               # [Rs, Lsp]
            smask = lane[None, :] < s_len[:, None]
            sidxc = jnp.clip(sidx, 0, M - 1)
            sdoc = d_doc[sidxc]
            # gather moves the packed f16 bytes; the cast to f32 (and the
            # exact IMPACT_SCALE shift back) happens in registers so the
            # scatter-add target stays full precision
            simp = d_imp[sidxc].astype(jnp.float32) * jnp.float32(
                IMPACT_SCALE)
            srs = d_rs[sidxc]
            scnt = d_cnt[sidxc]
            sdead = d_dead[jnp.clip(sdoc, 0, D - 1)]              # [Rs, Lsp]
            skeep = smask & ~(s_isbase[:, None] & sdead)
            tgt = jnp.where(skeep, s_group[:, None] * D + sdoc, T * D)
            ubs = jnp.zeros((T * D,), jnp.float32).at[tgt.ravel()].add(
                jnp.where(skeep, simp, 0.0).ravel(), mode="drop"
            ).reshape(T, D)
            ub = ubb * live[None, :] + ubs                        # [T, D]
            rstgt = jnp.where(
                smask, jnp.arange(Rs, dtype=jnp.int32)[:, None] * D + sdoc,
                Rs * D)
            rsacc = jnp.zeros((Rs * D,), jnp.int32).at[rstgt.ravel()].set(
                jnp.where(smask, srs, 0).ravel(), mode="drop")
            cntacc = jnp.zeros((Rs * D,), jnp.uint8).at[rstgt.ravel()].set(
                jnp.where(smask, scnt, jnp.uint8(0)).ravel(), mode="drop")

            # intersection + admissible min bound
            present = ub > 0.0                                    # [T, D]
            sc = counts
            ubw = ub * (freqw * freqw)[:, None]
            req_ok = jnp.all(jnp.where(required[:, None], present, True),
                             axis=0)
            neg_ok = ~jnp.any(jnp.where(negative[:, None], present, False),
                              axis=0)
            # the truth-table gate is a [D]-wide gather from a 1024-entry
            # table — ~140 ms/wave at B=64 (scalar gather) — and non-
            # boolean queries carry the all-true table, so the lookup is
            # compiled out unless the wave really holds boolean queries
            tok = presence_table_ok(present, table) if use_table else True
            alive = (req_ok & neg_ok & tok
                     & (jnp.arange(D) < n_docs_total))
            if use_filter:
                # numeric range gate (gbmin:/gbmax: — a host-ANDed boolean
                # column over however many fields the query constrained)
                alive = alive & d_filter
            m1 = present & sc[:, None]
            ubw_m = jnp.where(m1, ubw, big)
            min_single_ub = jnp.min(ubw_m, axis=0)
            # every pair is in the min, so the pair-bound min has a
            # closed form at any T: min over pairs of √(a_i·a_j) =
            # √(min1·min2) over the two smallest present scored bounds —
            # O(T·D) instead of a pair loop (~79 ms/wave at B=32)
            npres = jnp.sum(m1, axis=0)                           # [D]
            am = jnp.argmin(ubw_m, axis=0)                        # [D]
            min2 = jnp.min(
                jnp.where(t_ax[:, None] == am[None, :], big, ubw_m),
                axis=0)
            min_pair_ub = jnp.sqrt(min_single_ub * min2)
            any_pair = npres >= 2
            ubmin = jnp.minimum(jnp.where(any_pair, min_pair_ub, big),
                                min_single_ub)
            # per-doc filter-only fallback (mirrors scorer.min_scores)
            ubmin = jnp.where(jnp.any(m1, axis=0), ubmin, 1.0)
            mult = final_multipliers(d_siterank, d_doclang, qlang)
            if use_sort:
                # gbsortby: rank purely by the positive sort column — the
                # per-doc "bound" IS the exact sort key, so selection is
                # exact and the escalation check passes by construction
                ubfinal = jnp.where(alive, d_sort, 0.0)
            else:
                ubfinal = jnp.where(alive, ubmin * mult * 1.00001, 0.0)
            nm = jnp.sum(alive)

        with jax.named_scope("f1.select"):
            # candidate selection via top-8-per-block max-reduces:
            # approx_max_k/top_k lower to sort-like programs costing
            # hundreds of ms on a [B, 131072] axis (measured ~190 ms fixed
            # per wave); _block_topn is ~2 ms and its missed_max feeds the
            # SAME lossless escalation check
            cval, cand, ub_missed = _block_topn(ubfinal, kappa)

            # phase 2 scores only the top-k2 BY BOUND: the (k2+1)-th-best
            # bound folds into the missed-max, so an unscored candidate
            # that could have ranked triggers the same lossless escalation.
            # Phase-2 gather cost is ∝ rows·P·κ·B (the dominant wave cost
            # at ~13-56 Melem/s scalar gather), so κ=2048 rungs score 128
            # candidates, not 2048 — the selection rung and the scoring
            # width decouple
            kap2 = kappa
            if k2 < kappa:
                vals, idxs = jax.lax.top_k(cval, k2 + 1)
                cand = cand[idxs[:k2]]
                cval = vals[:k2]
                ub_missed = jnp.maximum(ub_missed, vals[k2])
                kap2 = k2

        with jax.named_scope("f1.phase2_score"):
            # ---- phase 2: exact scoring of the κ candidates ----
            dead_c = d_dead[cand]                                 # [κ]
            p_ax = jnp.arange(P, dtype=jnp.int32)[:, None]        # [P, 1]
            cube = jnp.zeros((T, P, kap2), jnp.uint32)
            pv = jnp.zeros((T, P, kap2), bool)

            def add_row(cube, pv, rs, cnt_c, group, base, quota, syn,
                        is_base):
                cnt = cnt_c.astype(jnp.int32)                     # [κ]
                cnt = jnp.where(is_base & dead_c, 0, cnt)
                q = p_ax - base                                   # [P, κ]
                sel = (q >= 0) & (q < jnp.minimum(cnt, quota)[None, :])
                src = rs[None, :] + q
                val = (d_payload[jnp.clip(src, 0, N - 1)]
                       | (syn.astype(jnp.uint32) << jnp.uint32(31)))
                gmask = (group == t_ax)[:, None, None]            # [T, 1, 1]
                cube = cube + jnp.where(sel, val, jnp.uint32(0))[None] \
                    * gmask.astype(jnp.uint32)
                pv = pv | (sel[None] & gmask)
                return cube, pv

            dslotc = jnp.clip(d_slot, 0, V - 1)[:, None] * D + cand[None, :]
            dense_rs_c = d_dense_rs[dslotc]
            dense_cnt_c = d_dense_cnt[dslotc]
            for r in range(Rd):
                rs_c = jnp.where(dgate[r], dense_rs_c[r], 0)
                cnt_c = jnp.where(dgate[r], dense_cnt_c[r], jnp.uint8(0))
                cube, pv = add_row(cube, pv, rs_c, cnt_c, d_group[r],
                                   d_base[r], d_quota[r], d_syn[r], True)
            for r in range(Rs):
                cube, pv = add_row(cube, pv, rsacc[r * D + cand],
                                   cntacc[r * D + cand], s_group[r],
                                   s_base[r], s_quota[r], s_syn[r],
                                   s_isbase[r])

            min_sc, present2 = min_scores(cube, pv, freqw, sc)
            req_ok2 = jnp.all(jnp.where(required[:, None], present2, True),
                              axis=0)
            neg_ok2 = ~jnp.any(jnp.where(negative[:, None], present2, False),
                               axis=0)
            tok2 = presence_table_ok(present2, table) if use_table \
                else True
            match2 = (req_ok2 & neg_ok2 & tok2
                      & (cval > 0.0) & (min_sc < big))
            if use_sort:
                final = jnp.where(match2, d_sort[cand], 0.0)
            else:
                final = jnp.where(
                    match2,
                    min_sc * final_multipliers(d_siterank[cand],
                                               d_doclang[cand], qlang),
                    0.0)
            ts, tl = jax.lax.top_k(final, k2)
            ti = cand[tl]
        return jnp.concatenate([
            jnp.atleast_1d(nm.astype(jnp.uint32)),
            jax.lax.bitcast_convert_type(jnp.atleast_1d(ub_missed),
                                         jnp.uint32),
            ti.astype(jnp.uint32),
            jax.lax.bitcast_convert_type(ts, jnp.uint32),
        ])

    # one lane after another, the live ones only: the per-lane work is
    # scalar gathers and scatters, which a vmap does not overlap across
    # lanes, and a pad lane's row stays zeros
    lanes = (d_slot, d_group, d_base, d_quota, d_syn, s_start, s_len,
             s_group, s_base, s_quota, s_syn, s_isbase, freqw, required,
             negative, scored, counts, table, qlang)

    def lane(b, out):
        return out.at[b].set(one(ubb_mm[b], *(x[b] for x in lanes)))

    return jax.lax.fori_loop(0, n_live, lane,
                             jnp.zeros((B, 2 + 2 * k2), jnp.uint32))


@partial(jax.jit, static_argnames=("n_positions", "lpost", "k2", "n_sel",
                                   "use_table", "use_filter",
                                   "use_sort"))
def _full_cube(d_payload, d_docc, d_cube, d_dense_cnt,
               d_siterank, d_doclang, d_dead, n_docs_total,
               d_filter, d_sort,
               c_slot, c_dslot, c_group, c_base, c_quota, c_syn,
               p_start, p_len, p_group, p_base, p_quota, p_syn, p_isbase,
               freqw, required, negative, scored, counts, table, qlang,
               n_positions: int, lpost: int, k2: int, n_sel: int,
               use_table: bool = True, use_filter: bool = False,
               use_sort: bool = False):
    """Full-corpus exact kernel (F2) for corpus-wide drivers.

    Builds the [T, P, D] position cube over the WHOLE doc axis — the
    heaviest terms from materialized cube rows (plain slices), the rest
    by a bounded posting-granular scatter — then runs the exact
    docIdLoop scoring (scorer.min_scores) on every doc at once. This is
    the reference's intersectLists10_r docIdLoop with the loop axis
    vectorized away; no pruning, no escalation ladder.
    Output format matches _two_phase."""
    D = d_dead.shape[0]
    N = d_payload.shape[0]
    P = n_positions
    P4 = P // 4
    Vc = d_cube.shape[0] // 4
    big = jnp.float32(9.99e8)

    def one(c_slot, c_dslot, c_group, c_base, c_quota, c_syn,
            p_start, p_len, p_group, p_base, p_quota, p_syn, p_isbase,
            freqw, required, negative, scored, counts, table, qlang):
        T = required.shape[0]
        Rc = c_slot.shape[0]
        Rp = p_start.shape[0]
        t_ax = jnp.arange(T)
        live = ~d_dead
        p_ax = jnp.arange(P, dtype=jnp.int32)[:, None]        # [P, 1]

        with jax.named_scope("f2.cube_rows"):
            cube = jnp.zeros((T, P, D), jnp.uint32)
            pv = jnp.zeros((T, P, D), bool)
            # materialized cube rows: slice + count-mask (cube rows are
            # always base postings, so the dead vector masks them)
            V = d_dense_cnt.shape[0] // D
            for r in range(Rc):
                gate = c_slot[r] >= 0
                # a slot's [P, D] row = its four quarter rows
                row = jax.lax.dynamic_slice(
                    d_cube, (jnp.clip(c_slot[r], 0, Vc - 1) * 4,
                             jnp.int32(0), jnp.int32(0)),
                    (4, P4, D)).reshape(P, D)
                cnt = jax.lax.dynamic_slice(
                    d_dense_cnt, (jnp.clip(c_dslot[r], 0, V - 1) * D,),
                    (D,)).astype(jnp.int32)
                # shift the row to the sublist's slot range [base,
                # base+quota): out[p] = row[p - base]. Done as a contiguous
                # dynamic_slice on a zero-padded [2P, D] image — a traced-
                # index take here lowers to a ~P·D scalar gather per row
                # per lane, measured as THE dominant F2 cost (~270 ms/wave)
                q = p_ax[:, 0] - c_base[r]                    # [P]
                padded = jnp.concatenate(
                    [jnp.zeros((P, D), row.dtype), row], axis=0)
                row = jax.lax.dynamic_slice(
                    padded,
                    (jnp.int32(P) - jnp.clip(c_base[r], 0, P)
                     .astype(jnp.int32), jnp.int32(0)), (P, D))
                pvr = ((q[:, None] >= 0)
                       & (q[:, None]
                          < jnp.minimum(cnt, c_quota[r])[None, :])
                       & live[None, :] & gate)
                val = row | (c_syn[r].astype(jnp.uint32) << jnp.uint32(31))
                gmask = (c_group[r] == t_ax)[:, None, None]
                cube = cube + jnp.where(pvr, val, jnp.uint32(0))[None] \
                    * gmask.astype(jnp.uint32)
                pv = pv | (pvr[None] & gmask)
        with jax.named_scope("f2.tail_scatter"):
            # posting-granular scatter rows (bigrams, deltas, small terms)
            lane = jnp.arange(lpost, dtype=jnp.int32)
            idx = p_start[:, None] + lane[None, :]                # [Rp, Lp]
            m = lane[None, :] < p_len[:, None]
            idxc = jnp.clip(idx, 0, N - 1)
            docc = d_docc[idxc]
            doc = (docc >> jnp.uint32(_OCC_BITS)).astype(jnp.int32)
            occ = (docc & jnp.uint32(_OCC_MASK)).astype(jnp.int32)
            pay = (d_payload[idxc]
                   | (p_syn[:, None].astype(jnp.uint32) << jnp.uint32(31)))
            dead_l = d_dead[jnp.clip(doc, 0, D - 1)]
            ok = (m & (occ < p_quota[:, None])
                  & ~(dead_l & p_isbase[:, None]))
            slot = p_base[:, None] + occ
            tgt = jnp.where(ok, (p_group[:, None] * P + slot) * D + doc,
                            T * P * D)
            cube = cube.reshape(-1).at[tgt.ravel()].add(
                jnp.where(ok, pay, jnp.uint32(0)).ravel(), mode="drop"
            ).reshape(T, P, D)
            pv = pv.reshape(-1).at[tgt.ravel()].set(
                ok.ravel(), mode="drop").reshape(T, P, D)

        with jax.named_scope("f2.score_match"):
            min_sc, present = min_scores(cube, pv, freqw, counts)
            req_ok = jnp.all(jnp.where(required[:, None], present, True),
                             axis=0)
            neg_ok = ~jnp.any(jnp.where(negative[:, None], present, False),
                              axis=0)
            tok = presence_table_ok(present, table) if use_table else True
            match = (req_ok & neg_ok & tok
                     & (jnp.arange(D) < n_docs_total) & (min_sc < big))
            if use_filter:
                match = match & d_filter
            if use_sort:
                final = jnp.where(match, d_sort, 0.0)
            else:
                final = jnp.where(
                    match, min_sc * final_multipliers(d_siterank, d_doclang,
                                                      qlang), 0.0)
            nm = jnp.sum(match)
        with jax.named_scope("f2.topk"):
            # block-winners then a cheap exact top-k over the winners;
            # escalation reruns with 4x the blocks, terminal at n_sel == D
            # where every doc is selected and missed is exactly 0
            w_vals, w_idx, missed = _block_topn(final, min(n_sel, D))
            ts, tl = jax.lax.top_k(w_vals, min(k2, min(n_sel, D)))
            ti = w_idx[tl]
        return jnp.concatenate([
            jnp.atleast_1d(nm.astype(jnp.uint32)),
            jax.lax.bitcast_convert_type(jnp.atleast_1d(missed),
                                         jnp.uint32),
            ti.astype(jnp.uint32),
            jax.lax.bitcast_convert_type(ts, jnp.uint32),
        ])

    return jax.vmap(one)(c_slot, c_dslot, c_group, c_base, c_quota,
                         c_syn, p_start, p_len, p_group, p_base, p_quota,
                         p_syn, p_isbase, freqw, required, negative,
                         scored, counts, table, qlang)


@partial(jax.jit, static_argnames=("n_positions", "lpost", "k2",
                                   "n_sel", "use_table", "use_filter",
                                   "use_sort"))
def _direct_cube(d_cube, d_payload, d_docc, d_siterank,
                 d_doclang, d_dead, n_docs_total, d_filter, d_sort,
                 g_quarter, g_qsyn, n_live,
                 p_start, p_len, p_group, p_base, p_quota, p_syn,
                 p_isbase,
                 freqw, required, negative, scored, counts, table, qlang,
                 n_positions: int, lpost: int, k2: int, n_sel: int,
                 use_table: bool = True, use_filter: bool = False,
                 use_sort: bool = False):
    """Direct full-corpus kernel (FD) — the F2 fast path for queries
    whose every group assembles from quarter-aligned base cube rows
    (1 sublist = full row; original+bigram = half+half;
    original+synonym+bigram = half+quarter+quarter — the slot_plan
    layouts).

    No per-query [T, P, D] cube is scattered together from per-lane
    dynamic slices, traced shifts and masked adds (measured as the
    dominant F2 cost at ~24 ms/query): the group planes are quarter-row
    gathers from the resident cube (quarter q of a term's [P, D] row
    holds its occurrences q·P/4..), with a zero payload marking an
    empty slot (real postings always carry densityrank ≥ 1, so
    payload ≠ 0 — a build-side invariant; the cube's last slot is kept
    all-zero as the absent-quarter target). Small non-cube sublists
    (bigrams, deltas) add through a BOUNDED posting-scatter tail —
    the same scatter the generic F2 runs, capped by the planner at
    FD_SCATTER_MAX_LANES. Scoring is the very same ``min_scores``
    every other path runs, so parity is bit-for-bit by construction.
    Output format matches _full_cube. ``n_live`` [1] int32 counts the
    wave's lanes that hold a query (the rest are padding): data, not a
    static, so it adds no program; the fused kernel skips the padding,
    this body scores it."""
    D = d_dead.shape[0]
    P = n_positions
    N = d_payload.shape[0]
    Vc4 = d_cube.shape[0]
    big = jnp.float32(9.99e8)

    from .pallas_scores import use_fused
    if use_fused(D):
        return _direct_cube_fused(
            d_cube, d_payload, d_docc, d_siterank, d_doclang, d_dead,
            n_docs_total, d_filter, d_sort, g_quarter, g_qsyn, n_live,
            p_start, p_len, p_group, p_base, p_quota, p_syn, p_isbase,
            freqw, required, negative, scored, counts, table, qlang,
            n_positions=n_positions, lpost=lpost, k2=k2, n_sel=n_sel,
            use_table=use_table, use_filter=use_filter,
            use_sort=use_sort)

    def one(g_quarter, g_qsyn, p_start, p_len, p_group, p_base,
            p_quota, p_syn, p_isbase, freqw, required, negative,
            scored, counts, table, qlang):
        T = required.shape[0]
        live = ~d_dead
        sc = counts
        with jax.named_scope("fd.cube_rows"):
            rows = d_cube[jnp.clip(g_quarter, 0, Vc4 - 1)]  # [T,4,P4,D]
            synbit = (g_qsyn.astype(jnp.uint32)
                      << jnp.uint32(31))[:, :, None, None]
            rows = jnp.where(rows != 0, rows | synbit, rows)
            rows = rows.reshape(T, P, D)
            pvr = (rows != 0) & live[None, None, :]               # [T, P, D]
            # dead docs' base values must not pollute scatter-adds below
            cube = jnp.where(pvr, rows, jnp.uint32(0))
        with jax.named_scope("fd.tail_scatter"):
            # posting-granular scatter tail (bigrams, deltas, small terms —
            # same semantics as _full_cube's scatter block)
            lane = jnp.arange(lpost, dtype=jnp.int32)
            idx = p_start[:, None] + lane[None, :]                # [Rp, Lp]
            m = lane[None, :] < p_len[:, None]
            idxc = jnp.clip(idx, 0, N - 1)
            docc = d_docc[idxc]
            doc = (docc >> jnp.uint32(_OCC_BITS)).astype(jnp.int32)
            occ = (docc & jnp.uint32(_OCC_MASK)).astype(jnp.int32)
            pay = (d_payload[idxc]
                   | (p_syn[:, None].astype(jnp.uint32) << jnp.uint32(31)))
            dead_l = d_dead[jnp.clip(doc, 0, D - 1)]
            ok = (m & (occ < p_quota[:, None])
                  & ~(dead_l & p_isbase[:, None]))
            slot = p_base[:, None] + occ
            tgt = jnp.where(ok, (p_group[:, None] * P + slot) * D + doc,
                            T * P * D)
            cube = cube.reshape(-1).at[tgt.ravel()].add(
                jnp.where(ok, pay, jnp.uint32(0)).ravel(), mode="drop"
            ).reshape(T, P, D)
            pvr = pvr.reshape(-1).at[tgt.ravel()].set(
                ok.ravel(), mode="drop").reshape(T, P, D)
        with jax.named_scope("fd.score_match"):
            min_sc, present = min_scores(cube, pvr, freqw, sc)
            req_ok = jnp.all(jnp.where(required[:, None], present, True),
                             axis=0)
            neg_ok = ~jnp.any(jnp.where(negative[:, None], present, False),
                              axis=0)
            tok = presence_table_ok(present, table) if use_table else True
            match = (req_ok & neg_ok & tok
                     & (jnp.arange(D) < n_docs_total) & (min_sc < big))
            if use_filter:
                match = match & d_filter
            if use_sort:
                final = jnp.where(match, d_sort, 0.0)
            else:
                final = jnp.where(
                    match, min_sc * final_multipliers(d_siterank, d_doclang,
                                                      qlang), 0.0)
            nm = jnp.sum(match)
        with jax.named_scope("fd.topk"):
            w_vals, w_idx, missed = _block_topn(final, min(n_sel, D))
            ts, tl = jax.lax.top_k(w_vals, min(k2, n_sel, D))
            ti = w_idx[tl]
        return jnp.concatenate([
            jnp.atleast_1d(nm.astype(jnp.uint32)),
            jax.lax.bitcast_convert_type(jnp.atleast_1d(missed),
                                         jnp.uint32),
            ti.astype(jnp.uint32),
            jax.lax.bitcast_convert_type(ts, jnp.uint32),
        ])

    return jax.vmap(one)(g_quarter, g_qsyn, p_start, p_len, p_group,
                         p_base, p_quota, p_syn, p_isbase, freqw,
                         required, negative, scored, counts, table,
                         qlang)


def _direct_cube_fused(d_cube, d_payload, d_docc, d_siterank,
                       d_doclang, d_dead, n_docs_total, d_filter,
                       d_sort, g_quarter, g_qsyn, n_live,
                       p_start, p_len, p_group, p_base, p_quota,
                       p_syn, p_isbase,
                       freqw, required, negative, scored, counts,
                       table, qlang,
                       n_positions: int, lpost: int, k2: int,
                       n_sel: int, use_table: bool, use_filter: bool,
                       use_sort: bool):
    """FD via the fused Pallas kernel: the per-query [T, P, D] cube
    never materializes in HBM — only the (usually small) posting TAIL
    is scattered in XLA; assembly of the resident quarter-rows and the
    whole scoring chain run tile-by-tile in VMEM
    (pallas_scores.fd_scores_fused). Same outputs as _direct_cube."""
    from .pallas_scores import fd_scores_fused

    D = d_dead.shape[0]
    P = n_positions
    N = d_payload.shape[0]
    B, T, _ = g_quarter.shape
    big = jnp.float32(9.99e8)

    # ---- XLA: per-query tail cubes (zeros when the query has none);
    # dead-masking for base tail postings happens HERE, so the kernel
    # only applies the dead mask to the resident quarters ----
    def tail_of(p_start, p_len, p_quota, p_group, p_base, p_syn,
                p_isbase):
        with jax.named_scope("fd.tail_scatter"):
            lane = jnp.arange(lpost, dtype=jnp.int32)
            idx = p_start[:, None] + lane[None, :]
            m = lane[None, :] < p_len[:, None]
            idxc = jnp.clip(idx, 0, N - 1)
            docc = d_docc[idxc]
            doc = (docc >> jnp.uint32(_OCC_BITS)).astype(jnp.int32)
            occ = (docc & jnp.uint32(_OCC_MASK)).astype(jnp.int32)
            pay = (d_payload[idxc]
                   | (p_syn[:, None].astype(jnp.uint32) << jnp.uint32(31)))
            dead_l = d_dead[jnp.clip(doc, 0, D - 1)]
            ok = (m & (occ < p_quota[:, None])
                  & ~(dead_l & p_isbase[:, None]))
            slot = p_base[:, None] + occ
            tgt = jnp.where(ok, (p_group[:, None] * P + slot) * D + doc,
                            T * P * D)
            return jnp.zeros((T * P * D,), jnp.uint32).at[tgt.ravel()].add(
                jnp.where(ok, pay, jnp.uint32(0)).ravel(), mode="drop"
            ).reshape(T, P, D)

    from .pallas_scores import (fd_scores_fused_notail,
                                fd_scores_fused_notail_t8)
    interp = jax.default_backend() == "cpu"
    with jax.named_scope("fd.fused_score"):
        if lpost == 0:
            # pure quarter-row wave: no tail cube at all
            notail = fd_scores_fused_notail_t8 if T == WIDE_T \
                else fd_scores_fused_notail
            ms, presbits = notail(
                g_quarter.reshape(B, T * 4),
                g_qsyn.reshape(B, T * 4).astype(jnp.int32), n_live,
                d_cube, d_dead.astype(jnp.int32).reshape(1, D),
                freqw, counts.astype(jnp.float32), T=T, P=P,
                interpret=interp)
        else:
            tails = jax.vmap(tail_of)(p_start, p_len, p_quota, p_group,
                                      p_base, p_syn, p_isbase)
            ms, presbits = fd_scores_fused(
                g_quarter.reshape(B, T * 4),
                g_qsyn.reshape(B, T * 4).astype(jnp.int32), n_live,
                d_cube, tails, d_dead.astype(jnp.int32).reshape(1, D),
                freqw, counts.astype(jnp.float32), T=T, P=P,
                interpret=interp)

    # ---- XLA tail: match gates + selection (cheap [T, D]/[D] work) --
    def finish(ms, bits, freqw, required, negative, counts, table,
               qlang):
        with jax.named_scope("fd.match"):
            t_ax = jnp.arange(T, dtype=jnp.int32)
            present = ((bits[None, :] >> t_ax[:, None]) & 1) > 0  # [T, D]
            req_ok = jnp.all(jnp.where(required[:, None], present, True),
                             axis=0)
            neg_ok = ~jnp.any(jnp.where(negative[:, None], present,
                                        False), axis=0)
            tok = presence_table_ok(present, table) if use_table else True
            match = (req_ok & neg_ok & tok
                     & (jnp.arange(D) < n_docs_total) & (ms < big))
            if use_filter:
                match = match & d_filter
            if use_sort:
                final = jnp.where(match, d_sort, 0.0)
            else:
                final = jnp.where(
                    match, ms * final_multipliers(d_siterank, d_doclang,
                                                  qlang), 0.0)
            nm = jnp.sum(match)
        with jax.named_scope("fd.topk"):
            w_vals, w_idx, missed = _block_topn(final, min(n_sel, D))
            ts, tl = jax.lax.top_k(w_vals, min(k2, n_sel, D))
            ti = w_idx[tl]
        return jnp.concatenate([
            jnp.atleast_1d(nm.astype(jnp.uint32)),
            jax.lax.bitcast_convert_type(jnp.atleast_1d(missed),
                                         jnp.uint32),
            ti.astype(jnp.uint32),
            jax.lax.bitcast_convert_type(ts, jnp.uint32),
        ])

    return jax.vmap(finish)(ms, presbits, freqw, required, negative,
                            counts, table, qlang)
