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
        from open_source_search_engine_tpu.query import engine
        import open_source_search_engine_tpu.query.devindex as dv

        coll, di = env
        os.environ["OSSE_PALLAS"] = pallas
        dv._direct_cube.clear_cache()
        for q, route in (("alpha beta", "fd"), ("alpha gamma", "fd"),
                         ("boxes dogs", "f2")):
            before = di.route_counts[route]
            dev = engine.search_device(coll, q, topk=8,
                                       site_cluster=False,
                                       with_snippets=False)
            assert di.route_counts[route] == before + 1, (q, route)
            host = engine.search(coll, q, topk=8, site_cluster=False,
                                 with_snippets=False)
            assert dev.total_matches == host.total_matches, q
            _assert_same_ranking(
                [r.docid for r in host.results],
                [r.score for r in host.results],
                [r.docid for r in dev.results],
                [r.score for r in dev.results], q)
