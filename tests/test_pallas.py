"""Fused Pallas scoring-kernel parity (interpret mode on CPU).

The fused kernels (pallas_scores.py) reimplement the scoring chain
and the FD assembly; on CPU CI they never run by default (use_fused
gates them to TPU backends), so these tests FORCE them through
interpret mode and pin them against the jnp reference path — both at
the min_scores unit seam and end-to-end through the FD route."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from open_source_search_engine_tpu.query import scorer
from open_source_search_engine_tpu.query.pallas_scores import (
    TILE_D, min_scores_fused)


def _rand_cube(rng, T, P, D, density=0.25, inlink_frac=0.1):
    wordpos = rng.integers(0, 200000, (T, P, D)).astype(np.uint32)
    hg = rng.integers(0, 11, (T, P, D)).astype(np.uint32)
    # force some inlink-text rows (spamw sqrt path + single-term pool)
    hg = np.where(rng.random((T, P, D)) < inlink_frac, 5, hg)
    den = rng.integers(1, 32, (T, P, D)).astype(np.uint32)
    spam = rng.integers(0, 16, (T, P, D)).astype(np.uint32)
    syn = rng.integers(0, 2, (T, P, D)).astype(np.uint32)
    payload = (wordpos | (hg << 18) | (den << 22) | (spam << 27)
               | (syn << 31))
    pv = rng.random((T, P, D)) < density
    cube = np.where(pv, payload, 0).astype(np.uint32)
    pv = cube != 0  # the build-side invariant the kernel relies on
    return cube, pv


class TestMinScoresFused:
    @pytest.mark.parametrize("T,seed", [(4, 0), (8, 1)])
    def test_parity_random_cube(self, T, seed):
        rng = np.random.default_rng(seed)
        P, D = 16, TILE_D * 2
        cube, pv = _rand_cube(rng, T, P, D)
        fw = (rng.random(T) * 0.5 + 0.2).astype(np.float32)
        counts = rng.random(T) < 0.7
        if not counts.any():
            counts[0] = True
        ref, _ = scorer.min_scores(jnp.asarray(cube), jnp.asarray(pv),
                                   jnp.asarray(fw),
                                   jnp.asarray(counts))
        pal = min_scores_fused(jnp.asarray(cube), jnp.asarray(fw),
                               jnp.asarray(counts), interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)

    def test_parity_empty_and_degenerate(self):
        T, P, D = 4, 16, TILE_D
        cube = np.zeros((T, P, D), np.uint32)
        fw = np.full(T, 0.5, np.float32)
        counts = np.ones(T, bool)
        ref, _ = scorer.min_scores(
            jnp.asarray(cube), jnp.asarray(cube != 0),
            jnp.asarray(fw), jnp.asarray(counts))
        pal = min_scores_fused(jnp.asarray(cube), jnp.asarray(fw),
                               jnp.asarray(counts), interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref))


def _fd_wave(rng, B, n_live, T, P, D, Vc, tail):
    """One FD wave as ``_run_batch_fd`` lays it out: a quarter-row cube
    [Vc·4, P/4, D] whose last slot is all zero, random queries in the
    first ``n_live`` lanes, and padding after them (every quarter on
    the zero slot, counts False, no tail)."""
    slots, _ = _rand_cube(rng, Vc - 1, P, D)
    cube = np.concatenate([slots.reshape((Vc - 1) * 4, P // 4, D),
                           np.zeros((4, P // 4, D), np.uint32)])
    zq = 4 * (Vc - 1)
    gq = np.full((B, T, 4), zq, np.int32)
    sy = np.zeros((B, T, 4), np.int32)
    fw = np.full((B, T), 0.5, np.float32)
    counts = np.zeros((B, T), np.float32)
    tails = np.zeros((B, T, P, D), np.uint32)
    for b in range(n_live):
        gq[b] = 4 * rng.integers(0, Vc - 1, T)[:, None] + np.arange(4)
        sy[b] = rng.integers(0, 2, (T, 4))
        fw[b] = rng.random(T) * 0.5 + 0.2
        counts[b] = rng.random(T) < 0.7
        counts[b, 0] = 1.0
        if tail:
            # the slot plan keeps tail postings off the quarter rows'
            rows = cube[gq[b].reshape(-1)].reshape(T, P, D)
            tails[b] = np.where(rows == 0, _rand_cube(
                rng, T, P, D, density=0.02)[0], 0)
    dead = (rng.random((1, D)) < 0.05).astype(np.int32)
    return (gq.reshape(B, T * 4), sy.reshape(B, T * 4), cube, tails,
            dead, fw, counts)


class TestFdPadLanes:
    """The fused FD kernel skips a wave's padding lanes (no DMA, no
    scoring) and writes what scoring them would: min_score 1.0 and
    presence 0. Every lane's output is bit for bit the kernel's with
    every lane scored, and each live lane scores its assembled cube as
    ``scorer.min_scores`` does."""

    @pytest.mark.parametrize("tail", [False, True],
                             ids=["notail", "tail"])
    @pytest.mark.parametrize("B,n_live", [(4, 1), (4, 2), (4, 3),
                                          (4, 4), (16, 5)])
    def test_pad_lanes_cost_nothing_and_answer_the_same(self, B, n_live,
                                                        tail):
        from open_source_search_engine_tpu.query.pallas_scores import (
            fd_scores_fused, fd_scores_fused_notail)
        T, P, D, Vc = 3, 16, TILE_D * 2, 8
        rng = np.random.default_rng(100 * B + n_live)
        gq, sy, cube, tails, dead, fw, counts = _fd_wave(
            rng, B, n_live, T, P, D, Vc, tail)

        def run(n):
            nl = np.array([n], np.int32)
            if tail:
                out = fd_scores_fused(gq, sy, nl, cube, tails, dead, fw,
                                      counts, T=T, P=P, interpret=True)
            else:
                out = fd_scores_fused_notail(gq, sy, nl, cube, dead, fw,
                                             counts, T=T, P=P,
                                             interpret=True)
            return [np.asarray(x) for x in out]

        ms, pres = run(n_live)
        ms_all, pres_all = run(B)
        assert ms.shape == (B, D) and pres.shape == (B, D)
        assert np.array_equal(ms.view(np.uint32), ms_all.view(np.uint32))
        assert np.array_equal(pres, pres_all)
        assert (ms[n_live:] == np.float32(1.0)).all()
        assert (pres[n_live:] == 0).all()
        # live lanes against the reference scoring of the assembled cube
        for b in range(n_live):
            rows = cube[gq[b]]                          # [T·4, P/4, D]
            rows = np.where(rows != 0,
                            rows | (sy[b].astype(np.uint32)
                                    << np.uint32(31))[:, None, None],
                            rows).reshape(T, P, D)
            lane = np.where(dead[0] == 0, rows, 0).astype(np.uint32)
            lane |= tails[b]
            ref, present = scorer.min_scores(
                jnp.asarray(lane), jnp.asarray(lane != 0),
                jnp.asarray(fw[b]), jnp.asarray(counts[b] > 0.5))
            np.testing.assert_allclose(ms[b], np.asarray(ref),
                                       rtol=1e-5, atol=1e-7)
            bits = (np.asarray(present).astype(np.int32)
                    << np.arange(T)[:, None]).sum(0)
            assert np.array_equal(pres[b], bits)


@pytest.mark.parametrize("tail", [False, True], ids=["notail", "tail"])
def test_wide_fd_kernel_rolls_its_pairs_and_answers_the_same(tail):
    """At ``T`` 8 the kernel rolls its 28 pairs (VMEM planes read by a
    dynamic group index), as at every ``T``, and runs under names of its
    own; each live lane
    scores its assembled cube as ``scorer.min_scores`` does, and a pad
    lane still writes ms 1.0 and presence 0."""
    from open_source_search_engine_tpu.query import pallas_scores as ps
    T, P, D, Vc, B, n_live = 8, 16, TILE_D * 2, 12, 4, 3
    assert T == ps.WIDE_T
    rng = np.random.default_rng(808)
    gq, sy, cube, tails, dead, fw, counts = _fd_wave(
        rng, B, n_live, T, P, D, Vc, tail)
    nl = np.array([n_live], np.int32)
    if tail:
        ms, pres = ps.fd_scores_fused(gq, sy, nl, cube, tails, dead, fw,
                                      counts, T=T, P=P, interpret=True)
    else:
        ms, pres = ps.fd_scores_fused_notail_t8(
            gq, sy, nl, cube, dead, fw, counts, T=T, P=P, interpret=True)
    ms, pres = np.asarray(ms), np.asarray(pres)
    assert (ms[n_live:] == np.float32(1.0)).all()
    assert (pres[n_live:] == 0).all()
    for b in range(n_live):
        rows = cube[gq[b]]
        rows = np.where(rows != 0,
                        rows | (sy[b].astype(np.uint32)
                                << np.uint32(31))[:, None, None],
                        rows).reshape(T, P, D)
        lane = np.where(dead[0] == 0, rows, 0).astype(np.uint32)
        lane |= tails[b]
        ref, present = scorer.min_scores(
            jnp.asarray(lane), jnp.asarray(lane != 0),
            jnp.asarray(fw[b]), jnp.asarray(counts[b] > 0.5))
        np.testing.assert_allclose(ms[b], np.asarray(ref), rtol=1e-5,
                                   atol=1e-7)
        bits = (np.asarray(present).astype(np.int32)
                << np.arange(T)[:, None]).sum(0)
        assert np.array_equal(pres[b], bits)


def _assert_same_ranking(ref_ids, ref_scores, ids, scores, label):
    """Scores agree to the last-ulp reduction order; docids agree at
    strictly-untied ranks (tie order is not part of the contract)."""
    np.testing.assert_allclose(scores, ref_scores, rtol=1e-5,
                               err_msg=label)
    for r in range(len(ref_scores)):
        tied = ((r > 0 and ref_scores[r - 1] == ref_scores[r])
                or (r + 1 < len(ref_scores)
                    and ref_scores[r + 1] == ref_scores[r]))
        if not tied:
            assert ref_ids[r] == ids[r], (label, r)


class TestFusedEndToEnd:
    def test_fd_route_matches_jnp_path(self, tmp_path):
        """Index a corpus whose common multi-term queries take the FD
        route, then compare the whole search output with the fused
        path forced (interpret) vs disabled."""
        from open_source_search_engine_tpu.build import docproc
        from open_source_search_engine_tpu.index.collection import \
            Collection
        from open_source_search_engine_tpu.parallel.routecheck import \
            ROUTE_ENV, route_docs
        from open_source_search_engine_tpu.query import engine
        import open_source_search_engine_tpu.query.devindex as dv

        saved = {k: os.environ.get(k) for k in
                 list(ROUTE_ENV) + ["OSSE_PALLAS"]}
        os.environ.update(ROUTE_ENV)
        try:
            coll = Collection("p", str(tmp_path))
            docproc.index_batch(coll, route_docs(256, "pal"))
            coll.posdb.dump()
            coll.titledb.dump()
            di = engine.get_device_index(coll)
            queries = ["alpha beta", "alpha gamma", "boxes dogs",
                       "alpha", "zeta"]
            outs = {}
            for flag in ("0", "force"):
                os.environ["OSSE_PALLAS"] = flag
                dv._direct_cube.clear_cache()
                di.route_counts = {"f1": 0, "fd": 0, "f2": 0}
                res = di.search_batch(queries, topk=8)
                outs[flag] = res
                if flag == "force":
                    assert di.route_counts["fd"] > 0  # FD exercised
            for q, a, b in zip(queries, outs["0"], outs["force"]):
                assert a[2] == b[2], q                   # n_matched
                _assert_same_ranking(a[0], a[1], b[0], b[1], q)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            dv._direct_cube.clear_cache()


class TestQuarterRowCube:
    """The resident cube has ONE form, quarter rows [Vc·4, P/4, D_cap]
    (the FD kernel's operand as it stands): the build hands back what
    the old flat scatter held, reshaped, and every reader — the fused
    FD kernel (interpret mode here), the jnp ``_direct_cube`` body and
    F2's ``_full_cube`` — answers from it as the host flat path does."""

    @pytest.fixture(scope="class")
    def env(self, tmp_path_factory):
        from open_source_search_engine_tpu.build import docproc
        from open_source_search_engine_tpu.index.collection import \
            Collection
        from open_source_search_engine_tpu.parallel.routecheck import \
            ROUTE_ENV, route_docs
        from open_source_search_engine_tpu.query import engine
        import open_source_search_engine_tpu.query.devindex as dv

        saved = {k: os.environ.get(k) for k in
                 list(ROUTE_ENV) + ["OSSE_PALLAS"]}
        os.environ.update(ROUTE_ENV)
        coll = Collection("q", str(tmp_path_factory.mktemp("qrc")))
        coll.conf.pqr_enabled = False   # kernel parity: pre-PQR scores
        docproc.index_batch(coll, route_docs(256, "qrc"))
        coll.posdb.dump()
        coll.titledb.dump()
        yield coll, engine.get_device_index(coll)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        dv._direct_cube.clear_cache()

    def test_build_hands_back_the_flat_scatter_as_quarter_rows(self, env):
        _, di = env
        P, D, Vc = di.P, di.D_cap, di.Vc
        assert len(di.cube_slot_of) > 0
        payload = np.asarray(di.d_payload)
        docc = np.asarray(di.d_docc)
        flat = np.zeros(Vc * P * D, np.uint32)      # the former form
        for termid, slot in di.cube_slot_of.items():
            ti = int(np.searchsorted(di.dir_termids, np.uint64(termid)))
            a, b = int(di.dir_pstart[ti]), int(di.dir_pstart[ti + 1])
            occ = (docc[a:b] & 0xF).astype(np.int64)
            doc = (docc[a:b] >> 4).astype(np.int64)
            flat[(slot * P + occ) * D + doc] = payload[a:b]
        cube = np.asarray(di.d_cube)
        assert cube.shape == (Vc * 4, P // 4, D)
        assert flat.any()
        assert np.array_equal(cube, flat.reshape(Vc * 4, P // 4, D))
        assert not cube[4 * di.cube_zero_slot:].any()  # absent quarter

    @pytest.mark.parametrize("pallas", ["0", "force"],
                             ids=["jnp", "fused"])
    def test_fd_and_f2_answer_as_the_host_flat_path(self, env, pallas):
        """One query a batch (an FD wave of three pad lanes), then five
        FD queries in one batch (a B 16 wave of eleven)."""
        from open_source_search_engine_tpu.query import engine
        import open_source_search_engine_tpu.query.devindex as dv

        coll, di = env
        os.environ["OSSE_PALLAS"] = pallas
        dv._direct_cube.clear_cache()

        def same_as_host(q, dev):
            host = engine.search(coll, q, topk=8, site_cluster=False,
                                 with_snippets=False)
            assert dev.total_matches == host.total_matches, q
            _assert_same_ranking(
                [r.docid for r in host.results],
                [r.score for r in host.results],
                [r.docid for r in dev.results],
                [r.score for r in dev.results], q)

        for q, route in (("alpha beta", "fd"), ("alpha gamma", "fd"),
                         ("boxes dogs", "f2")):
            before = di.route_counts[route]
            lanes = _lanes("fd")
            dev = engine.search_device(coll, q, topk=8,
                                       site_cluster=False,
                                       with_snippets=False)
            assert di.route_counts[route] == before + 1, (q, route)
            if route == "fd":
                assert _lanes("fd") == (lanes[0] + 4, lanes[1] + 3), q
            same_as_host(q, dev)
        before = di.route_counts["fd"]
        lanes = _lanes("fd")
        devs = engine.search_device_batch(coll, FD_FIVE, topk=8,
                                          site_cluster=False,
                                          with_snippets=False)
        assert di.route_counts["fd"] == before + 5
        assert _lanes("fd") == (lanes[0] + 16, lanes[1] + 11)
        for q, dev in zip(FD_FIVE, devs):
            same_as_host(q, dev)

    @pytest.mark.parametrize("n", [1, 5])
    def test_an_fd_wave_counts_its_pad_lanes_and_keeps_its_key(
            self, env, monkeypatch, n):
        """``_run_batch_fd`` counts B lanes and B - n pad lanes, hands
        the kernel the live count as data, and dispatches the program
        key and statics the bucket rule has always given."""
        import open_source_search_engine_tpu.query.devindex as dv
        from open_source_search_engine_tpu.query.compiler import \
            compile_query

        _, di = env
        plans = [di.plan(compile_query(q, 0)) for q in FD_FIVE[:n]]
        seen = []

        def note(self, name, bucket, modeled, fn, *args, **statics):
            seen.append((name, tuple(int(x) for x in bucket), args,
                         statics))
        monkeypatch.setattr(dv.DeviceIndex, "_costed", note)
        lanes = _lanes("fd")
        di._run_batch_fd(plans, 128, 2048)
        # the bucket rule, as it stood before pad lanes were skipped
        B = 4 if n <= 4 else max(di._fd_bmax(), n)
        T = max(len(p.required) for p in plans)
        mrp = max([len(p.p_start) for p in plans] + [1])
        Rp = 4 if mrp <= 4 else dv._bucket(mrp, 8)
        assert _lanes("fd") == (lanes[0] + B, lanes[1] + B - n)
        assert B == (4 if n == 1 else 16)
        [(name, bucket, args, statics)] = seen
        assert name == "devindex._direct_cube"
        assert bucket == (B, T, Rp, 0, 128, min(2048, di.D_cap))
        assert set(statics) == {"n_positions", "lpost", "k2", "n_sel",
                                "use_table", "use_filter", "use_sort"}
        assert statics["lpost"] == 0 and statics["k2"] == 128
        # g_quarter, g_qsyn, then the live count: [n] int32, traced
        assert args[9].shape == (B, T, 4) and args[10].shape == (B, T, 4)
        assert args[11].dtype == np.int32 and args[11].tolist() == [n]

    def test_an_f1_wave_counts_its_pad_lanes_and_keeps_its_key(
            self, env, monkeypatch):
        """``_run_batch`` with one plan counts 4 lanes and 3 pad lanes,
        hands ``_two_phase`` the live count as data, and dispatches the
        program key the tier rule has always given: one of the closed
        set, which is what it was."""
        import open_source_search_engine_tpu.query.devindex as dv
        from open_source_search_engine_tpu.query.compiler import \
            compile_query

        _, di = env
        plan = di.plan(compile_query("zeta", 0))
        seen = []

        def note(self, name, bucket, modeled, fn, *args, **statics):
            seen.append((name, tuple(int(x) for x in bucket), args,
                         statics))
        monkeypatch.setattr(dv.DeviceIndex, "_costed", note)
        lanes = _lanes("f1")
        di._run_batch([plan], 256, 256)
        assert _lanes("f1") == (lanes[0] + 4, lanes[1] + 3)
        [(name, bucket, args, statics)] = seen
        assert name == "devindex._two_phase"
        mls = int(plan.s_len.max()) if len(plan.s_len) else 0
        tier = dv._f1_rows(max(len(plan.d_slot), 1),
                           max(len(plan.s_start), 1), mls)
        assert bucket == (4, *tier, 256, 256)
        assert set(statics) == {"n_positions", "lsp", "kappa", "k2",
                                "use_table", "use_filter", "use_sort"}
        # the selector, then the live count: an int32 scalar, traced
        assert args[14].shape[0] == 4
        assert args[15].dtype == np.int32 and int(args[15]) == 1
        # the closed F1 set at this D_cap (the rungs fold at 2048)
        assert di.D_cap == 2048
        assert di.f1_programs() == [
            (4, 4, 2, 128, 256, 256), (4, 4, 2, 512, 256, 256),
            (4, 4, 4, 512, 256, 256), (4, 4, 4, 2048, 256, 256),
            (4, 16, 16, 2048, 256, 256), (4, 4, 4, 2048, 2048, 2048),
            (4, 16, 16, 2048, 2048, 2048),
            (4, 16, 16, 2048, 256, 256, 8),
            (4, 16, 16, 2048, 2048, 2048, 8)]
        assert bucket in di.f1_programs()


@pytest.mark.parametrize("counters,want", [
    ({"devindex.fd.lanes": 8.0, "devindex.fd.pad_lanes": 5.0}, 62.5),
    ({"devindex.fd.lanes": 16.0, "devindex.fd.pad_lanes": 0.0}, 0.0),
    ({"query": 40.0}, None),            # the parent: no such counters
    ({"devindex.fd.lanes": 0.0, "devindex.fd.pad_lanes": 0.0}, None),
])
def test_fd_pad_share_reads_the_counters_or_nothing(counters, want):
    import importlib.util
    from pathlib import Path
    path = (Path(__file__).resolve().parents[1] / "benchmarks"
            / "layer_metrics" / "fd_pad_share.py")
    spec = importlib.util.spec_from_file_location("fd_pad_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"counters": counters}) == want


@pytest.mark.parametrize("counters,want", [
    ({"devindex.f1.lanes": 8.0, "devindex.f1.pad_lanes": 5.0}, 62.5),
    ({"devindex.f1.lanes": 4.0, "devindex.f1.pad_lanes": 0.0}, 0.0),
    ({"devindex.fd.lanes": 8.0, "devindex.fd.pad_lanes": 5.0}, None),
    ({"query": 40.0}, None),            # the parent: no such counters
    ({"devindex.f1.lanes": 0.0, "devindex.f1.pad_lanes": 0.0}, None),
])
def test_f1_pad_share_reads_the_counters_or_nothing(counters, want):
    import importlib.util
    from pathlib import Path
    path = (Path(__file__).resolve().parents[1] / "benchmarks"
            / "layer_metrics" / "f1_pad_share.py")
    spec = importlib.util.spec_from_file_location("f1_pad_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"counters": counters}) == want


#: FD queries of the route corpus that meet in one pure quarter-row wave
FD_FIVE = ["alpha beta", "alpha gamma", "beta gamma", "alpha boxes",
           "beta dogs"]


def _lanes(route: str) -> tuple[float, float]:
    """(lanes, pad lanes) the ``route`` ("fd" or "f1") waves counted."""
    from open_source_search_engine_tpu.utils.stats import g_stats
    c = g_stats.snapshot()["counters"]
    return (c.get(f"devindex.{route}.lanes", 0),
            c.get(f"devindex.{route}.pad_lanes", 0))
