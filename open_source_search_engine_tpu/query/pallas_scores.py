"""Fused Pallas scoring kernels — the scoring chain in ONE HBM pass.

Two kernels share one scoring body (the exact ``scorer.min_scores``
math — payload decode, position weights, single-term top-10 sums, P×P
pair cross products, the min):

* ``min_scores_fused``: [T, P, D] cube already in HBM → min_score [D].
  Replaces the ~30-pass XLA lowering for the generic F2 kernel and the
  host-packed path on corpus-wide doc axes.
* ``fd_scores_fused``: the direct-cube (FD) route WITHOUT ever
  materializing the [T, P, D] cube in HBM: a scalar-prefetch grid DMAs
  each query's T×4 quarter-rows of the RESIDENT cube tile-by-tile into
  VMEM, ORs in the (XLA-scattered) tail cube and the dead mask, scores
  the tile on-chip, and writes one f32 + one presence bitmask per doc.
  The FD assembly chain (gather + synbit + masks, measured ~27 ms/query
  at 250k docs) and the scoring chain (~30 ms) collapse into a single
  bandwidth-bound pass.

Float reduction order differs from the jnp path in the last ulp, which
every consumer tolerates (escalation tolerance 1e-4, bench recall
floor 1e-6); the jnp path remains the reference semantics and the
small-cube / CPU path. Validity rides the payloads: zero payload =
empty slot (the build-side invariant the FD route already relies on).

Packed-layout contract (SURVEY §7 stage-8): these kernels consume the
uint32 payload cubes ONLY — never the f16 impact bounds or uint8
siterank/langid columns the packed index demotes (those feed phase-1
selection and the final multipliers, both outside this kernel). That
is what makes the demotion score-exact: the exact rescore path through
here reads bits the packing never touched.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..index.posdb import HASHGROUP_END, HASHGROUP_INLINKTEXT
from . import weights
from .packer import WIDE_T
from .scorer import QDIST

#: doc-axis tile width (lane-dim multiple of 128). Sized UP to 1024:
#: the FD grid runs T·4 steps per (query, tile), so step-dispatch
#: overhead — not bandwidth — floors the wave time; 1024-wide tiles
#: halve the step count while the working set (~8 MB at T=8: decode
#: products + two live [P, P, TILE] pair buffers + the [T·4, P4,
#: TILE] cube scratch) still fits v5e's ~16 MB VMEM.
TILE_D = 1024

#: use the fused kernels only where they pay: corpus-wide doc axes.
#: Small phase-2 cubes (κ ≤ 2048) fuse fine under plain XLA.
MIN_D = 8192


def _sel_chain(idx, table):
    """Tiny-table lookup as a select chain (same trick as
    scorer._tiny_lookup — in-register, no gather)."""
    out = jnp.full(idx.shape, float(table[0]), jnp.float32)
    for v in range(1, len(table)):
        out = jnp.where(idx == v, jnp.float32(table[v]), out)
    return out


def pair_planes(T: int, P: int, TD: int):
    """VMEM scratch the pair loop reads by a dynamic group index:
    word positions, position weights, flags (in body | valid << 1)
    [T, P, TD], and each group's term-frequency weight where the group
    is present and scored, else -1 [T, 1, TD]."""
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((T, P, TD), jnp.int32),
            pltpu.VMEM((T, P, TD), jnp.float32),
            pltpu.VMEM((T, P, TD), jnp.int32),
            pltpu.VMEM((T, 1, TD), jnp.float32)]


def _pair_ij(k, T: int):
    """Pair ``k`` of the table (0, 1), (0, 2) .. (T-2, T-1) as scalar
    (i, j), by a select chain over the rows' first pair indices."""
    first = [sum(T - 1 - r for r in range(i)) for i in range(T - 1)]
    i, base = jnp.int32(0), jnp.int32(0)
    for r in range(1, T - 1):
        hit = k >= first[r]
        i = jnp.where(hit, jnp.int32(r), i)
        base = jnp.where(hit, jnp.int32(first[r]), base)
    return i, k - base + i + 1


def _score_tile(cube, fw, cnt, T: int, P: int, planes):
    """The scoring body on one [T, P, TD] VMEM tile → (min_score [TD],
    presence bitmask [TD] int32). Bit-for-bit the scorer.min_scores
    math (modulo reduction order), over every term pair; ``planes`` are
    ``pair_planes``' refs."""
    big = jnp.float32(9.99e8)

    valid = cube != 0
    wordpos = (cube & jnp.uint32(0x3FFFF)).astype(jnp.int32)
    hg = ((cube >> jnp.uint32(18)) & jnp.uint32(0xF)).astype(jnp.int32)
    den = ((cube >> jnp.uint32(22)) & jnp.uint32(0x1F)).astype(
        jnp.int32)
    spam = ((cube >> jnp.uint32(27)) & jnp.uint32(0xF)).astype(
        jnp.int32)
    syn = ((cube >> jnp.uint32(31)) & jnp.uint32(1)).astype(jnp.int32)
    hgw = _sel_chain(hg, weights.HASH_GROUP_WEIGHTS)
    denw = jnp.minimum(
        jnp.float32(0.35) * jnp.exp(den.astype(jnp.float32)
                                    * jnp.float32(np.log(1.03445))),
        1.0)
    spamf = spam.astype(jnp.float32)
    spamw = jnp.where(hg == HASHGROUP_INLINKTEXT,
                      jnp.sqrt(1.0 + spamf),
                      (spamf + 1.0) * jnp.float32(1.0 / 16.0))
    synw = jnp.where(syn == 1, jnp.float32(weights.SYNONYM_WEIGHT),
                     jnp.float32(1.0))
    posw = hgw * denw * spamw * synw                      # [T, P, TD]
    posscore = (jnp.float32(weights.BASE_SCORE) * posw * posw
                * valid.astype(jnp.float32))
    present = jnp.any(valid, axis=1)                      # [T, TD]

    # singles: top-MAX_TOP over {mapped-hashgroup maxima} ∪ {inlink
    # occurrences} (getSingleTermScore)
    mhg = _sel_chain(hg, weights.MAPPED_HASHGROUP).astype(jnp.int32)
    is_inlink = hg == HASHGROUP_INLINKTEXT
    cands = []
    for g in range(HASHGROUP_END):
        if g == HASHGROUP_INLINKTEXT:
            cands.append(jnp.zeros((T, cube.shape[2]), jnp.float32))
        else:
            cands.append(jnp.max(
                jnp.where(mhg == g, posscore, 0.0), axis=1))
    for p in range(P):
        cands.append(jnp.where(is_inlink[:, p], posscore[:, p], 0.0))
    cand = jnp.stack(cands, axis=1)               # [T, G+P, TD]
    k10 = min(weights.MAX_TOP, cand.shape[1])
    iota_c = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
    top_sum = jnp.zeros((T, cube.shape[2]), jnp.float32)
    work = cand
    for _ in range(k10):
        m = jnp.max(work, axis=1)
        top_sum = top_sum + m
        am = jnp.argmax(work, axis=1)
        work = jnp.where(iota_c == am[:, None, :],
                         jnp.float32(-1.0), work)
    single = top_sum * (fw * fw)[:, None]         # [T, TD]

    # expand dims on the f32 BEFORE comparing: Mosaic cannot reshape
    # sub-32-bit (i1) vectors along the minor dim
    s_mask = present & (cnt[:, None] > 0.5)
    min_single = jnp.min(jnp.where(s_mask, single, big), axis=0)

    # pairs: exact max over P×P for every pair (i, j) (pair_best)
    in_body = _sel_chain(hg, weights.IN_BODY) > 0.5       # [T, P, TD]
    # one loop over the pair table, reading each group's planes from
    # VMEM by a dynamic index: the body is traced once, so a program's
    # compile does not grow with its pairs (T 8: 28)
    wp_ref, pw_ref, fl_ref, gw_ref = planes
    wp_ref[...] = wordpos
    pw_ref[...] = posw
    fl_ref[...] = (in_body.astype(jnp.int32)
                   | (valid.astype(jnp.int32) << 1))
    for t in range(T):
        gw_ref[t] = jnp.where(s_mask[t], fw[t], jnp.float32(-1.0))[None, :]

    def pair(k, min_pair):
        i, j = _pair_ij(k, T)
        fl_i, fl_j = fl_ref[i], fl_ref[j]
        gw_i, gw_j = gw_ref[i][0], gw_ref[j][0]
        delta = (wp_ref[j][None, :, :]
                 - wp_ref[i][:, None, :]).astype(jnp.float32)
        d_plain = jnp.maximum(jnp.abs(delta), 2.0)        # [P, P, TD]
        bi = ((fl_i & 1) > 0)[:, None, :]
        bj = ((fl_j & 1) > 0)[None, :, :]
        mixed = bi != bj
        both_nb = (~bi) & (~bj)
        d_base = jnp.where(
            both_nb & (d_plain > weights.NONBODY_DIST_CAP),
            jnp.float32(weights.FIXED_DISTANCE), d_plain)
        d_adj = (jnp.where(d_base >= QDIST, d_base - QDIST, d_base)
                 + (delta < 0))
        dist = jnp.where(mixed, jnp.float32(weights.FIXED_DISTANCE),
                         d_adj)
        pvij = ((fl_i & 2) > 0)[:, None, :] & ((fl_j & 2) > 0)[None, :, :]
        ps = (jnp.float32(weights.BASE_SCORE)
              * pw_ref[i][:, None, :] * pw_ref[j][None, :, :]
              / (dist + 1.0)) * pvij
        wts = jnp.max(ps, axis=(0, 1)) * gw_i * gw_j      # [TD]
        pair_ok = (gw_i >= 0.0) & (gw_j >= 0.0)
        return jnp.where(pair_ok, jnp.minimum(min_pair, wts), min_pair)

    # no pair present leaves min_pair at big
    min_pair = jax.lax.fori_loop(0, T * (T - 1) // 2, pair,
                                 jnp.full(min_single.shape, big))

    ms = jnp.minimum(min_pair, min_single)
    ms = jnp.where(jnp.any(s_mask, axis=0), ms, jnp.float32(1.0))
    # presence bitmask (T ≤ 16 bits): callers unpack for req/neg/table
    pres = jnp.zeros(ms.shape, jnp.int32)
    for t in range(T):
        pres = pres | (present[t].astype(jnp.int32) << t)
    return ms, pres


# --------------------------------------------------------------- F2 path

def _ms_kernel(cube_ref, fw_ref, cnt_ref, out_ref, *planes, T: int,
               P: int):
    ms, _ = _score_tile(cube_ref[0], fw_ref[0], cnt_ref[0], T, P, planes)
    out_ref[0] = ms


def _guard_cube(cube, route: str):
    """Devcheck sweep on a concrete cube before kernel dispatch: every
    nonzero payload must decode to a legal hashgroup. Host-side and
    opt-in (query.devcheck); a no-op under tracing — callers already
    inside a jit get their sweep at the devindex dispatch layer."""
    from . import devcheck
    if not devcheck.enabled() or isinstance(cube, jax.core.Tracer):
        return cube
    cube = devcheck.apply_cube_fault(cube)
    devcheck.check_cube(cube, route=route)
    return cube


def min_scores_fused(cube, freqw, counts, interpret: bool = False):
    """[T, P, D] uint32 cube → min_score [D] f32 (validity = payload
    ≠ 0). ``counts`` bool [T]. Batched callers vmap this; pallas lifts
    the batch axis into the grid."""
    cube = _guard_cube(cube, "pallas.f2")
    return _min_scores_fused(cube, freqw, counts, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _min_scores_fused(cube, freqw, counts, interpret: bool = False):
    from jax.experimental import pallas as pl

    T, P, D = cube.shape
    assert D % TILE_D == 0, (T, P, D)
    fw = freqw.astype(jnp.float32).reshape(1, T)
    cnt = counts.astype(jnp.float32).reshape(1, T)
    cube4 = cube.reshape(1, T, P, D)
    out = pl.pallas_call(
        functools.partial(_ms_kernel, T=T, P=P),
        grid=(D // TILE_D,),
        in_specs=[
            pl.BlockSpec((1, T, P, TILE_D),
                         lambda d: (0, 0, 0, d)),
            pl.BlockSpec((1, T), lambda d: (0, 0)),
            pl.BlockSpec((1, T), lambda d: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, TILE_D), lambda d: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        scratch_shapes=pair_planes(T, P, TILE_D),
        interpret=interpret,
    )(cube4, fw, cnt)
    return out[0]


# --------------------------------------------------------------- FD path

def _fd_kernel(gq_ref, syn_ref, nlive_ref, rows_hbm, *rest, T: int,
               P: int, has_tail: bool):
    """Grid (B, D/TILE): ONE step per (query, doc tile). The step
    issues T·4 async DMAs pulling the query's quarter-row slices from
    the HBM-resident cube straight into the VMEM scratch (a grid axis
    per quarter paid ~8 µs of step dispatch to move 16 KB — the DMA
    form is ~16× fewer steps), waits, assembles, scores. Waves whose
    every query is pure quarter-rows (no posting tail — the common FD
    case) compile WITHOUT the tail input, skipping a cube-sized HBM
    write+read per query.

    Lanes from ``nlive_ref[0]`` on are the wave's padding: their cube
    is all zero and their counts False, so they score exactly
    (ms 1.0, presence 0) — written as such, with no DMA and no
    scoring."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_tail:
        tail_ref, dead_ref, fw_ref, cnt_ref, ms_ref, pres_ref, \
            acc_ref, sems, *planes = rest
    else:
        dead_ref, fw_ref, cnt_ref, ms_ref, pres_ref, acc_ref, \
            sems, *planes = rest

    b = pl.program_id(0)
    d = pl.program_id(1)
    TQ = T * 4
    TD = acc_ref.shape[2]
    lane_live = b < nlive_ref[0]

    def dma(tq):
        return pltpu.make_async_copy(
            rows_hbm.at[gq_ref[b, tq], :, pl.dslice(d * TD, TD)],
            acc_ref.at[tq], sems.at[tq])

    @pl.when(lane_live)
    def _score():
        for tq in range(TQ):
            dma(tq).start()
        for tq in range(TQ):
            dma(tq).wait()

        # per-quarter synonym bit, read from the prefetched scalars and
        # OR'd in place (a [TQ]→[TQ,1,1] vector broadcast is an
        # unsupported Mosaic shape cast; the scalar form also skips the
        # no-synonym common case entirely)
        for tq in range(TQ):
            sb = (syn_ref[b, tq].astype(jnp.uint32) << jnp.uint32(31))

            @pl.when(sb != 0)
            def _orsyn(tq=tq, sb=sb):
                r = acc_ref[tq]
                acc_ref[tq] = jnp.where(r != 0, r | sb, r)

        rows = acc_ref[...]                         # [T·4, P4, TD]
        live = dead_ref[0] == 0                     # [TD]
        cube = jnp.where(live[None, None, :], rows.reshape(T, P, TD),
                         jnp.uint32(0))
        if has_tail:
            # tail postings were dead-filtered at scatter time (delta
            # postings of re-added docs live PAST the dead mask) — OR
            # after masking. Slot ranges are disjoint by the slot plan.
            cube = cube | tail_ref[0]
        ms, pres = _score_tile(cube, fw_ref[0, 0], cnt_ref[0, 0], T, P,
                               planes)
        ms_ref[0, 0] = ms
        pres_ref[0, 0] = pres

    @pl.when(jnp.logical_not(lane_live))
    def _pad():
        ms_ref[0, 0] = jnp.ones((TD,), jnp.float32)
        pres_ref[0, 0] = jnp.zeros((TD,), jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("T", "P", "interpret"))
def _fd_scores_fused(g_quarter, g_qsyn, n_live, d_cube, tail_cube,
                     dead_i32, freqw, counts, T: int, P: int,
                     interpret: bool = False):
    return _fd_call(g_quarter, g_qsyn, n_live, d_cube, tail_cube,
                    dead_i32, freqw, counts, T=T, P=P,
                    interpret=interpret, has_tail=True)


@functools.partial(jax.jit,
                   static_argnames=("T", "P", "interpret"))
def fd_scores_fused_t8(g_quarter, g_qsyn, n_live, d_cube, tail_cube,
                       dead_i32, freqw, counts, T: int, P: int,
                       interpret: bool = False):
    """``_fd_scores_fused`` at ``WIDE_T`` (five to eight words): a
    program of its own name, which the device trace tells apart."""
    return _fd_call(g_quarter, g_qsyn, n_live, d_cube, tail_cube,
                    dead_i32, freqw, counts, T=T, P=P,
                    interpret=interpret, has_tail=True)


def fd_scores_fused(g_quarter, g_qsyn, n_live, d_cube, tail_cube,
                    dead_i32, freqw, counts, T: int, P: int,
                    interpret: bool = False):
    """Tail-carrying variant (see _fd_kernel)."""
    d_cube = _guard_cube(d_cube, "pallas.fd")
    fn = fd_scores_fused_t8 if T == WIDE_T else _fd_scores_fused
    return fn(g_quarter, g_qsyn, n_live, d_cube, tail_cube, dead_i32,
              freqw, counts, T=T, P=P, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("T", "P", "interpret"))
def fd_scores_fused_notail(g_quarter, g_qsyn, n_live, d_cube, dead_i32,
                           freqw, counts, T: int, P: int,
                           interpret: bool = False):
    """No-tail variant: pure quarter-row waves."""
    return _fd_call(g_quarter, g_qsyn, n_live, d_cube, None, dead_i32,
                    freqw, counts, T=T, P=P, interpret=interpret,
                    has_tail=False)


@functools.partial(jax.jit,
                   static_argnames=("T", "P", "interpret"))
def fd_scores_fused_notail_t8(g_quarter, g_qsyn, n_live, d_cube,
                              dead_i32, freqw, counts, T: int, P: int,
                              interpret: bool = False):
    """``fd_scores_fused_notail`` at ``T`` 8 (see
    ``fd_scores_fused_t8``)."""
    return _fd_call(g_quarter, g_qsyn, n_live, d_cube, None, dead_i32,
                    freqw, counts, T=T, P=P, interpret=interpret,
                    has_tail=False)


def _fd_call(g_quarter, g_qsyn, n_live, d_cube, tail_cube, dead_i32,
             freqw, counts, T: int, P: int,
             interpret: bool, has_tail: bool):
    """The direct-cube route, fused: returns (min_score [B, D] f32,
    presence bitmask [B, D] int32).

    ``g_quarter``/``g_qsyn`` [B, T·4] int32 — absolute quarter-row
    indices into the resident cube + per-quarter synonym flags;
    ``n_live`` [1] int32 — the wave's lanes that hold a query, the
    first ``n_live`` (the rest are padding, answered without work);
    ``d_cube`` the resident cube as it is built and kept, quarter rows
    [Vc·4, P/4, D]: the kernel's HBM operand as it stands, so no wave
    program copies or relayouts it; ``tail_cube`` [B, T, P, D] uint32
    — the XLA-scattered posting tail (zeros where the query has none);
    ``dead_i32`` [1, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, TQ = g_quarter.shape
    assert TQ == 4 * T
    D = dead_i32.shape[1]
    assert D % TILE_D == 0
    assert d_cube.shape[1:] == (P // 4, D), d_cube.shape
    # (B, 1, T) so every block dim equals an array dim (Mosaic requires
    # sublane block dims to match the array or divide 8)
    fw = freqw.astype(jnp.float32).reshape(B, 1, T)
    cnt = counts.astype(jnp.float32).reshape(B, 1, T)
    n_tiles = D // TILE_D

    def held(b, d, nl):
        """(lane, tile) of the block a step reads: a padding lane keeps
        the last live lane's last tile, the block already in VMEM, so
        the pipeline fetches nothing for it."""
        live = b < nl[0]
        return (jnp.where(live, b, jnp.maximum(nl[0] - 1, 0)),
                jnp.where(live, d, n_tiles - 1))

    def tail_block(b, d, gq, syn, nl):
        lane, tile = held(b, d, nl)
        return lane, 0, 0, tile

    def dead_block(b, d, gq, syn, nl):
        return 0, held(b, d, nl)[1]

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.ANY),   # resident rows: HBM
    ]
    operands = [d_cube]
    if has_tail:
        in_specs.append(pl.BlockSpec((1, T, P, TILE_D), tail_block))
        operands.append(tail_cube)
    in_specs += [
        pl.BlockSpec((1, TILE_D), dead_block),
        pl.BlockSpec((1, 1, T),
                     lambda b, d, gq, syn, nl: (b, 0, 0)),
        pl.BlockSpec((1, 1, T),
                     lambda b, d, gq, syn, nl: (b, 0, 0)),
    ]
    operands += [dead_i32, fw, cnt]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,       # g_quarter, g_qsyn, n_live
        grid=(B, n_tiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, TILE_D),
                         lambda b, d, gq, syn, nl: (b, 0, d)),
            pl.BlockSpec((1, 1, TILE_D),
                         lambda b, d, gq, syn, nl: (b, 0, d)),
        ],
        scratch_shapes=[
            pltpu.VMEM((T * 4, P // 4, TILE_D), jnp.uint32),
            pltpu.SemaphoreType.DMA((T * 4,)),
        ] + pair_planes(T, P, TILE_D),
    )
    # no scope of its own: the kernel's op takes the name of the
    # innermost scope, the jit it is called under: ``_fd_scores_fused``
    # / ``fd_scores_fused_notail``, the names the ledger's
    # ``breakdown.device_ops`` has always held, and their ``_t8`` twins
    # at ``WIDE_T``
    ms, pres = pl.pallas_call(
        functools.partial(_fd_kernel, T=T, P=P, has_tail=has_tail),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, D), jnp.int32)],
        interpret=interpret,
    )(g_quarter, g_qsyn, n_live, *operands)
    return ms[:, 0], pres[:, 0]


def use_fused(D: int) -> bool:
    """Route policy: fused kernels on TPU backends for corpus-wide doc
    axes (OSSE_PALLAS=0 disables; =force enables everywhere, which
    tests use with interpret mode on CPU)."""
    mode = os.environ.get("OSSE_PALLAS", "1")
    if mode == "0":
        return False
    if mode == "force":
        return D % TILE_D == 0
    return (D >= MIN_D and D % TILE_D == 0
            and jax.default_backend() not in ("cpu",))
