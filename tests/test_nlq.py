"""Questions of five to eight words: every term pair at every ``T``.

The configuration ``gbshard-nlq-80k`` asks the text shard MS MARCO-shaped
questions, so every query lands in the ``T`` 8 bucket: its score is the min
over all 28 term pairs (the weakest pair sets it, and that is often the pair
of two distant words), F1 rides a closed family of ``T`` 8 programs, and the
fused FD kernel rolls its pair loop so that its compile does not grow with
the pairs. ``T`` 4 answers stay what they were, bit for bit.

The corpus generator, the query rule, the plain reference and the metric
readers are the benchmark's own files (``benchmarks/``), loaded by path.
"""

import importlib.util
import json
import os
from collections import Counter
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.parallel.routecheck import ROUTE_ENV
from open_source_search_engine_tpu.query import devindex, engine, scorer
from open_source_search_engine_tpu.query.compiler import compile_query
from open_source_search_engine_tpu.query.pallas_scores import (
    TILE_D, min_scores_fused)
from open_source_search_engine_tpu.utils.stats import g_stats

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
GOLDEN = Path(__file__).resolve().parent / "golden" / "t4_text_answers.json"
DOCS, SEED = 400, 3838
#: the cell's classes are shares of 80,000 pages; at 400 pages the same
#: rule needs wider ones (Low: 1-3 pages, Med: 4-14, High: 16 and more)
CLASSES = {"stop_words": 8, "high_min": 0.04, "med": [0.01, 0.035],
           "low": [0.0025, 0.0075]}
SPAN = 4        # the rule every pair replaced: pairs (i, j) with j - i <= 4


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"t_nlq_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _count(name: str) -> float:
    return g_stats.snapshot()["counters"].get(name, 0)


@pytest.fixture(scope="module")
def routes_env():
    """The route-exercise thresholds (dense and cube rows at a few pages,
    F2 past 16), so that 400 pages route questions to FD as well as F1."""
    saved = {k: os.environ.get(k) for k in list(ROUTE_ENV) + ["OSSE_PALLAS"]}
    os.environ.update(ROUTE_ENV)
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    devindex._direct_cube.clear_cache()


@pytest.fixture(scope="module")
def nlq(routes_env, tmp_path_factory):
    """400 pages of the text corpus, indexed; the question rule's list."""
    cfg = json.loads((BENCH / "configs" / "gbshard-nlq-80k.json").read_text())
    mix = json.loads((BENCH / "traffic" / "q5to8-c32.json").read_text())
    gen, rule = _load("corpora", "heaps_text"), _load("queries", "nlq_questions")
    p = cfg["corpus"]["params"]
    qp = json.loads(json.dumps(mix["queries"]["params"]))
    qp["corpus"]["docs"], qp["classes"] = DOCS, CLASSES
    coll = Collection("main", tmp_path_factory.mktemp("nlq"))
    coll.conf.pqr_enabled = False
    docproc.index_batch(coll, list(gen.pages(SEED, 0, DOCS, p)))
    coll.dump_all()
    lens, ids = gen.word_ids(SEED, 0, DOCS, p)
    return {"cfg": cfg, "gen": gen, "rule": rule, "p": p, "qp": qp,
            "coll": coll, "lens": lens, "ids": ids,
            "queries": rule.make(SEED, 120, qp),
            "ref": _load("reference", "gb_minscore").Reference(lens, ids, p),
            "compare": _load("lib", "compare")}


def _rows(results) -> list[dict]:
    return [{"q": i, "status": 200, "body": json.dumps({
        "totalMatches": r.total_matches,
        "results": [{"url": x.url, "score": x.score} for x in r.results]})}
        for i, r in enumerate(results)]


# ------------------------------------------------- the served path, at T 8

@pytest.mark.parametrize("pallas", ["0", "force"], ids=["jnp", "fused"])
def test_questions_match_the_plain_reference(nlq, pallas):
    """Scores and the ten best to 1e-4, exact ``totalMatches``, two a site,
    through ``engine.search_device_batch``: the comparison that decides
    ``correct``, on FD (the fused kernel in interpret mode, or the jnp body)
    and F1 at ``T`` 8."""
    os.environ["OSSE_PALLAS"] = pallas
    devindex._direct_cube.clear_cache()
    qs = nlq["queries"][:40]
    before = {r: _count(f"devindex.route.{r}.t8") for r in ("f1", "fd")}
    res = engine.search_device_batch(nlq["coll"], qs, topk=10,
                                     with_snippets=False)
    ok, numbers, notes = nlq["compare"].compare(
        _rows(res), qs, nlq["ref"].answer, nlq["gen"].doc_of_url, 2, 0.0,
        nlq["cfg"]["check"]["limits"])
    assert ok, (numbers, notes)
    assert all(r.total_matches >= 1 for r in res)    # each its page at least
    assert all(_count(f"devindex.route.{r}.t8") > before[r]
               for r in before)


def test_wide_route_counters_count_the_t8_queries(nlq):
    di = engine.get_device_index(nlq["coll"])
    names = {r: f"devindex.route.{r}" for r in ("f1", "fd", "f2")}
    before = {k: _count(v) for k, v in names.items()}
    wide = {k: _count(v + ".t8") for k, v in names.items()}
    mixed = nlq["queries"][40:60] + ["word1 word2", "word3"]
    engine.search_device_batch(nlq["coll"], mixed, topk=10,
                               with_snippets=False)
    moved = {k: _count(v) - before[k] for k, v in names.items()}
    moved_wide = {k: _count(v + ".t8") - wide[k] for k, v in names.items()}
    assert sum(moved.values()) == 22
    assert sum(moved_wide.values()) == 20        # the two short ones: T 4
    assert all(moved_wide[k] <= moved[k] for k in moved)
    assert di.route_counts["fd"] > 0


# ------------------------------------------------------ the control: (0, 7)

def _control_corpus():
    """Page 0 holds the question's eight words in its body: the first near
    the top, the six middle ones side by side, the last near the end, so
    the pair (0, 7) is the weakest; pages 1-23 are filler."""
    rng = np.random.default_rng(7)
    lens = rng.integers(110, 221, 24).astype(np.int32)
    lens[0] = 200
    pages = [rng.integers(1000, 5000, n).astype(np.int32) for n in lens]
    body = pages[0]
    body[10] = 1
    body[100:106] = np.arange(2, 8)
    body[195] = 8
    return lens, np.concatenate(pages)


def _span_score(ref, words: list[int], page: int) -> float:
    """The reference's score of ``page`` with pairs cut to ``SPAN``."""
    planes = []
    for k, w in enumerate(words):
        pages, pl, _ = ref._group(w, words[k + 1] if k + 1 < len(words)
                                  else None)
        row = np.searchsorted(pages, [page])
        planes.append(({key: v[row] for key, v in pl.items()}, len(pages)))
    tfw = [0.5 + min(df / ref.n_docs, 0.5) for _, df in planes]
    best = np.inf
    for i, (g, _) in enumerate(planes):
        best = min(best, float(ref._single(g)[0]) * tfw[i] ** 2)
        for j in range(i + 1, min(i + 1 + SPAN, len(planes))):
            best = min(best, float(ref._pair_best(g, planes[j][0])[0])
                       * tfw[i] * tfw[j])
    return best * 20.0       # the reference's language boost


def test_the_far_pair_sets_the_score(monkeypatch, tmp_path):
    """The served answer equals the reference's, whose minimum is the pair
    (0, 7); the old span rule, which never scored (0, 7), reads higher by
    far more than the check's 1e-4."""
    gen, cfg = _load("corpora", "heaps_text"), json.loads(
        (BENCH / "configs" / "gbshard-nlq-80k.json").read_text())
    p = cfg["corpus"]["params"]
    lens, ids = _control_corpus()
    off = np.r_[0, np.cumsum(lens)]
    monkeypatch.setattr(gen, "word_ids", lambda seed, lo, hi, p: (
        lens[lo:hi], ids[off[lo]:off[hi]]))
    coll = Collection("main", tmp_path)
    coll.conf.pqr_enabled = False
    docproc.index_batch(coll, list(gen.pages(0, 0, len(lens), p)))
    coll.dump_all()
    q = " ".join(f"word{w}" for w in range(1, 9))
    [res] = engine.search_device_batch(coll, [q], topk=10,
                                       with_snippets=False)
    ref = _load("reference", "gb_minscore").Reference(lens, ids, p)
    ok, numbers, notes = _load("lib", "compare").compare(
        _rows([res]), [q], ref.answer, gen.doc_of_url, 2, 0.0,
        cfg["check"]["limits"])
    assert ok and res.total_matches == 1, (numbers, notes)
    # the weakest pair of the reference's own min is (0, 7) ...
    words = list(range(1, 9))
    g = [ref._group(w, words[k + 1] if k < 7 else None)[1]
         for k, w in enumerate(words)]
    pair = {(i, j): float(ref._pair_best(g[i], g[j])[0])
            for i in range(8) for j in range(i + 1, 8)}
    assert min(pair, key=pair.get) == (0, 7)
    # ... and the span rule misses it by more than the check allows
    want = ref.answer(q)["ladder"][0]
    assert _span_score(ref, words, 0) > want * (1 + 1e-3)
    assert abs(res.results[0].score - want) <= 1e-4 * want


def test_the_fused_kernel_scores_the_far_pair():
    """The same at the kernel: a hand-built ``T`` 8 cube whose pair (0, 7)
    is the weakest; the fused kernel (interpret mode, rolled pair loop)
    gives the jnp path's every-pair min, below the span rule's."""
    T, P, D = 8, 16, TILE_D
    pos = [10, 100, 101, 102, 103, 104, 105, 300]
    pay = lambda wp: (wp | (0 << 18) | (20 << 22) | (15 << 27))
    cube = np.zeros((T, P, D), np.uint32)
    for t, wp in enumerate(pos):
        cube[t, 0, :] = pay(wp + np.arange(D) % 7)
    fw = np.full(T, 0.6, np.float32)
    counts = np.ones(T, bool)
    ref, _ = scorer.min_scores(jnp.asarray(cube), jnp.asarray(cube != 0),
                               jnp.asarray(fw), jnp.asarray(counts))
    got = min_scores_fused(jnp.asarray(cube), jnp.asarray(fw),
                           jnp.asarray(counts), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    _, posw, wordpos, hg = scorer.position_weights(
        jnp.asarray(cube), jnp.asarray(cube != 0))
    in_body = jnp.ones_like(hg, bool)
    pv = jnp.asarray(cube != 0)

    def pair(i, j):
        return scorer.pair_best(posw[i], wordpos[i], in_body[i], pv[i],
                                posw[j], wordpos[j], in_body[j], pv[j]) \
            * fw[i] * fw[j]
    span = jnp.min(jnp.stack([pair(i, j) for i in range(T)
                              for j in range(i + 1, min(i + 1 + SPAN, T))]),
                   axis=0)
    assert np.allclose(np.asarray(ref), np.asarray(pair(0, 7)))
    assert (np.asarray(span) > np.asarray(ref) * 1.1).all()


# ----------------------------------------------------- T 4 stays as it was

def _t4_answers(tmp_path) -> dict:
    """T 4 queries (1-4 words of a page; two in three of words in more than
    20 pages, so FD takes them) on 300 pages of the text corpus, through
    the served path with the jnp bodies and with the fused kernels."""
    gen = _load("corpora", "heaps_text")
    p = json.loads((BENCH / "configs" / "gbshard-text-80k.json"
                    ).read_text())["corpus"]["params"]
    seed, docs = 3737, 300
    coll = Collection("main", tmp_path)
    coll.conf.pqr_enabled = False
    docproc.index_batch(coll, list(gen.pages(seed, 0, docs, p)))
    coll.dump_all()
    lens, ids = gen.word_ids(seed, 0, docs, p)
    off = np.r_[0, np.cumsum(lens)]
    df = gen.doc_freq(lens, ids, p)
    rng = np.random.default_rng(37)
    queries = []
    for k in range(48):
        d = int(rng.integers(docs))
        ws = list(dict.fromkeys(int(w) for w in ids[off[d]:off[d + 1]]))
        if k % 3:
            ws = [w for w in ws if df[w] > 20]
        pick = sorted(rng.choice(len(ws), 1 + k % 4, replace=False))
        queries.append(" ".join(f"word{ws[i]}" for i in pick))
    out = {}
    for mode in ("0", "force"):
        os.environ["OSSE_PALLAS"] = mode
        for fn in (devindex._direct_cube, devindex._full_cube,
                   devindex._two_phase):
            fn.clear_cache()
        di = engine.get_device_index(coll)
        was = dict(di.route_counts)
        rs = engine.search_device_batch(coll, queries, topk=10,
                                        with_snippets=False)
        out[mode] = {
            "routes": {k: di.route_counts[k] - was[k] for k in was},
            "answers": [[r.total_matches,
                         [[int(x.docid), float(x.score).hex()]
                          for x in r.results]] for r in rs]}
    return {"seed": seed, "docs": docs, "queries": queries, "modes": out}


def test_t4_answers_are_byte_for_byte_what_they_were(routes_env, tmp_path):
    """The golden file holds these answers as the program gave them before
    every pair was scored (then pairs of at most four apart: all six of
    ``T`` 4's): the same docids, scores to the bit, counts and routes."""
    want = json.loads(GOLDEN.read_text())
    got = _t4_answers(tmp_path)
    assert got["queries"] == want["queries"]
    for mode in ("0", "force"):
        assert got["modes"][mode]["routes"] == want["modes"][mode]["routes"]
        assert got["modes"][mode]["routes"]["fd"] > 0
        assert got["modes"][mode]["answers"] == \
            want["modes"][mode]["answers"], mode


# ------------------------------------------ F1's closed family at T 8

@pytest.fixture
def no_dispatch(monkeypatch):
    keys = []

    def note(self, name, bucket, modeled_bytes, fn, *args, **statics):
        keys.append((name, tuple(int(x) for x in bucket)))
        return None
    monkeypatch.setattr(devindex.DeviceIndex, "_costed", note)
    return keys


def test_any_merge_of_wide_f1_plans_rides_an_enumerated_program(
        nlq, no_dispatch):
    """The F1_TIERS invariant at ``T`` 8: whatever plans of five to eight
    plain words ``_issue_waves`` puts into one ``_run_batch`` call (up to
    four a wave), the key is one of the three the index enumerated, and
    carries its ``T``."""
    di = engine.get_device_index(nlq["coll"])
    enumerated = set(di.f1_programs())
    wide = {k for k in enumerated if len(k) == 7}
    assert 1 <= len(wide) <= 3 and all(k[-1] == 8 for k in wide)
    plans = [di.plan(compile_query(q, 0)) for q in nlq["queries"]]
    plans = [p for p in plans if p.matchable]
    assert plans and all(len(p.required) == 8 for p in plans)
    rng = np.random.default_rng(37)
    before = _count("devindex.f1.key_outside_set")
    for n in [int(rng.integers(1, 5)) for _ in range(150)] + [4, 9, 17]:
        pick = [plans[i] for i in rng.choice(len(plans), n, replace=False)]
        for p in pick:
            p.kappa_min = int(rng.choice([0, 2048, 8192]))
        del no_dispatch[:]
        di._issue_waves(pick, list(range(n)), [], 64, 128, 2048, 4)
        assert no_dispatch
        for name, bucket in no_dispatch:
            assert name == "devindex._two_phase"
            assert bucket in wide, bucket
    assert _count("devindex.f1.key_outside_set") == before
    for p in plans:
        p.kappa_min = 0


def test_warm_f1_leaves_no_f1_program_to_compile(nlq):
    """Start-up traces the twelve (here, at a small ``D_cap``, nine: the
    rungs fold) one after another, compiles them side by side, dispatches
    each once; an F1 wave of either bucket then compiles nothing."""
    from open_source_search_engine_tpu.utils import jitwatch
    was = jitwatch.enabled()
    jitwatch.enable()
    try:
        di = devindex.DeviceIndex(nlq["coll"])
        jitwatch.reset()
        assert di.warm_f1() == len(di.f1_programs())
        assert jitwatch.snapshot()["totals"]["compiles"] >= 1
        assert set(b for _, b in di.dispatches) == set(di.f1_programs())
        jitwatch.reset()
        plans = [di.plan(compile_query(q, 0))
                 for q in nlq["queries"][60:64] + ["word1 word2", "word3"]]
        di._issue_waves(plans, list(range(len(plans))), [], 64, 128, 2048, 4)
        assert jitwatch.snapshot()["totals"]["compiles"] == 0
    finally:
        jitwatch.reset()
        if not was:
            jitwatch.disable()


@pytest.mark.parametrize("T,tier", [(4, (4, 4, 512)), (8, (16, 16, 2048))],
                         ids=["t4", "t8"])
@pytest.mark.parametrize("n_live", [1, 2, 3, 4])
def test_an_f1_wave_scores_its_live_lanes_only(nlq, monkeypatch, T, tier,
                                               n_live):
    """A ``B`` 4 ``_two_phase`` wave of ``n_live`` plans: each live row is
    bit for bit that plan's row in a wave whose four lanes are live, and
    each pad row is zeros (the lanes past ``n_live`` run nothing)."""
    di = engine.get_device_index(nlq["coll"])
    qs = [" ".join(q.split()[:3]) if T == 4 else q for q in nlq["queries"]]

    def fits(p):
        mls = int(p.s_len.max()) if len(p.s_len) else 0
        return (p.matchable and not p.has_table and len(p.required) == T
                and len(p.d_slot) <= tier[0] and len(p.s_start) <= tier[1]
                and mls <= tier[2] and len(p.d_slot) + len(p.s_start) > 1)
    plans = [p for p in (di.plan(compile_query(q, 0)) for q in qs)
             if fits(p)][:4]
    assert len(plans) == 4
    # every wave on the tier under test, whatever rows its riders hold
    monkeypatch.setattr(devindex, "_f1_rows", lambda *a, **k: tier)

    def rows(ps):
        bucket, _, args, statics = di._f1_call(ps, 256, 256)
        assert bucket[:4] == (4, *tier) and int(args[15]) == len(ps)
        return np.asarray(devindex._two_phase(*args, **statics))
    full, part = rows(plans), rows(plans[:n_live])
    assert full.shape == part.shape == (4, 2 + 2 * 256)
    assert (full[:, 0] >= 1).all()          # each plan matches its page
    assert np.array_equal(part[:n_live], full[:n_live])
    assert not part[n_live:].any()


# ---------------------------------------------------- the question rule

def test_question_rule_lengths_stop_words_classes_and_uniqueness(nlq):
    rule, qp = nlq["rule"], nlq["qp"]
    queries = nlq["queries"]
    lens, ids, code = rule.corpus(SEED, qp)
    shapes = [rule.shape_of(q, code) for q in queries]
    for lo in range(0, len(queries), 20):
        block = shapes[lo:lo + 20]
        assert Counter(s[0] for s in block) == Counter(dict(qp["block"]))
        assert Counter(s[1] for s in block) == Counter(
            {n: k for n, k in qp["lengths"]})
    assert all(1 <= s[2] <= 3 for s in shapes)
    assert len(set(queries)) == len(queries)
    for q in queries:
        assert len(set(q.split())) == len(q.split())   # no word twice
    assert rule.make(SEED, 50, qp) == queries[:50]
    few = json.loads(json.dumps(qp))
    few["stop_words_per_question"] = [9, 9]
    with pytest.raises(RuntimeError, match="no unseen"):
        rule.make(SEED, 20, few)


def test_each_question_is_its_pages_words_in_order(nlq):
    """Every question's words occur in one page, in the question's order,
    so each matches at least that page."""
    lens, ids = nlq["lens"], nlq["ids"]
    off = np.r_[0, np.cumsum(lens)]
    first = []
    for d in range(len(lens)):
        words = ids[off[d]:off[d + 1]]
        u, at = np.unique(words, return_index=True)
        first.append(dict(zip(u.tolist(), at.tolist())))
    for q in nlq["queries"]:
        ws = [int(t[4:]) for t in q.split()]
        assert any(all(w in f for w in ws)
                   and [f[w] for w in ws] == sorted(f[w] for w in ws)
                   for f in first), q


def test_the_all_pairs_deployment_refuses_a_scorer_that_caps_the_span(
        monkeypatch):
    """``single_chip_all_pairs`` serves as ``single_chip`` where the scorer
    takes every pair, and ends a run at once, before anything compiles,
    where it leaves pairs out of the min (``scorer.MAX_PAIR_SPAN``)."""
    monkeypatch.syspath_prepend(str(BENCH))
    mod = _load("deployments", "single_chip_all_pairs")
    monkeypatch.setattr(mod._base.Deployment, "reach_chip",
                        lambda self: {"platform": "tpu"})
    dep = mod.Deployment.__new__(mod.Deployment)
    assert dep.reach_chip() == {"platform": "tpu"}
    monkeypatch.setattr(scorer, "MAX_PAIR_SPAN", 4, raising=False)
    with pytest.raises(RuntimeError, match="farther apart than 4"):
        dep.reach_chip()


# ---------------------------------------------------- the six readers

def _reader(monkeypatch, name: str):
    monkeypatch.syspath_prepend(str(BENCH))
    return _load("layer_metrics", name)


ROUTES = {"devindex.route.f1": 40.0, "devindex.route.fd": 50.0,
          "devindex.route.f2": 10.0}
WIDE = {**ROUTES, "devindex.route.f1.t8": 40.0, "devindex.route.fd.t8": 50.0,
        "devindex.fd.t8_tail": 25.0}
OPS = [["fd_scores_fused_t8.1", 0.2], ["fd_scores_fused_notail_t8.1", 0.05],
       ["_fd_scores_fused.1", 1.0], ["fusion.3", 0.4]]
PEAKS = {"hbm_bytes_per_s": 819e9}
MODULES = {"jit__two_phase(1)": 0.5, "jit__two_phase(7)": 0.1,
           "jit__direct_cube(2)": 1.0}


@pytest.mark.parametrize("name,ctx,want", [
    ("t8_fd_share", {"counters": WIDE}, 50.0),
    ("t8_fd_share", {"counters": ROUTES}, None),         # the parent
    ("t8_f1_share", {"counters": WIDE}, 40.0),
    ("t8_f1_share", {"counters": ROUTES}, None),
    ("f1_keys_outside", {"counters": {
        "devindex.f1.key_outside_set": 3.0}}, 3.0),
    ("f1_keys_outside", {"counters": ROUTES}, 0.0),      # closed
    ("fd_t8_device_ms", {"counters": WIDE, "answers_in_span": 100,
                         "trace": {"ops": OPS}}, 5.0),
    ("fd_t8_device_ms", {"counters": ROUTES, "answers_in_span": 100,
                         "trace": {"ops": OPS}}, None),
    ("fd_t8_device_ms", {"counters": WIDE, "answers_in_span": 100,
                         "trace": {"ops": OPS[2:]}}, None),
    # 50 answers: 67,108,864 B each and half of them a tail as much again
    ("fd_t8_roofline", {"counters": WIDE, "answers_in_span": 100,
                        "trace": {"ops": OPS}, "peaks": PEAKS},
     100.0 * (50 * 67108864 * 1.5 / 819e9) / 0.25),
    ("fd_t8_roofline", {"counters": ROUTES, "answers_in_span": 100,
                        "trace": {"ops": OPS}, "peaks": PEAKS}, None),
    # 40 F1 answers at T 8, every F1 answer of the window at T 8
    ("f1_t8_device_ms", {"counters": WIDE, "answers_in_span": 100,
                         "trace": {"modules": MODULES}}, 1000.0 * 0.6 / 40),
    ("f1_t8_device_ms", {"counters": ROUTES, "answers_in_span": 100,
                         "trace": {"modules": MODULES}}, None),
    ("f1_t8_device_ms", {"counters": {**WIDE, "devindex.route.f1.t8": 30.0},
                         "answers_in_span": 100,
                         "trace": {"modules": MODULES}}, None),   # T 4 too
    ("f1_t8_device_ms", {"counters": WIDE, "answers_in_span": 100,
                         "trace": {"modules": {"jit__direct_cube(2)": 1.0}}},
     None),
])
def test_wide_readers_read_the_counters_and_names_or_nothing(
        monkeypatch, name, ctx, want):
    got = _reader(monkeypatch, name).read(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
