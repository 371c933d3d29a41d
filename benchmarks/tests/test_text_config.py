"""``gbshard-text-80k`` by hand: the generator's slices, the traffic file against
the configuration, the four metrics PR 30 brought, and the harness's own rules
for the new entries (additions only, names, lengths)."""

import json
import subprocess

import numpy as np
import pytest

from _helpers import BENCH  # noqa: F401 -- also puts benchmarks/ on the path
from corpora import heaps_text
from lib import spec

CFG = spec.load_json(BENCH / "configs" / "gbshard-text-80k.json")
MIX = spec.load_json(BENCH / "traffic" / "mix4-c32.json")
P = CFG["corpus"]["params"]


def test_corpus_slices_are_the_same_pages():
    whole = list(heaps_text.pages(9, 0, 2500, P))
    part = list(heaps_text.pages(9, 1990, 2010, P))
    assert part == whole[1990:2010]
    assert heaps_text.doc_of_url(part[0][0]) == 1990
    assert heaps_text.doc_of_url("http://x/other") is None
    lens, ids = heaps_text.word_ids(9, 0, 2500, P)
    l2, i2 = heaps_text.word_ids(9, 1990, 2010, P)
    off = np.r_[0, np.cumsum(lens)]
    assert ids.dtype == np.int32 and (l2 == lens[1990:2010]).all()
    assert (i2 == ids[off[1990]:off[2010]]).all()
    # a page as the reference reads it: the title is its first words again,
    # five words in the url, sentences of sentence_words
    url, html = part[0]
    words = [f"word{w}" for w in i2[:l2[0]]]
    assert html.startswith("<html><head><title>"
                           + " ".join(words[:P["title_words"]]) + "</title>")
    assert url == f"http://site{1990 % P['sites']}.bench.test/doc1990"
    # a large seed, as the driver's are
    assert heaps_text.word_ids(2**31 + 7, 0, 10, P)[1].max() < P["vocab"]


def test_postings_and_document_frequencies_by_hand():
    p = {**P, "vocab": 6, "title_words": 2}
    lens = np.array([3, 2], np.int32)
    ids = np.array([1, 1, 5, 5, 0], np.int32)         # pages [1 1 5] [5 0]
    assert heaps_text.postings_per_word(lens, ids, p).tolist() \
        == [2, 4, 0, 0, 0, 3]       # body + the title's first two words
    assert heaps_text.doc_freq(lens, ids, p).tolist() == [1, 1, 0, 0, 0, 2]


def test_the_traffic_file_names_the_configurations_corpus():
    """The query rule counts df from the generator's own pages: the traffic
    file's generator, parameters and page count are the configuration's."""
    c = MIX["queries"]["params"]["corpus"]
    assert c["generator"] == CFG["corpus"]["generator"]
    assert c["params"] == CFG["corpus"]["params"]
    assert c["docs"] == CFG["docs"]
    assert MIX["clients"] == 32 and MIX["loop"] == "closed"
    assert sum(n for _, n in MIX["queries"]["params"]["block"]) == 20
    assert MIX["walk"]["wide_plan"] <= 6000
    old = spec.load_json(BENCH / "configs" / "gbshard-80k.json")
    assert CFG["check"] == old["check"]
    assert CFG["guarantees"] == old["guarantees"]
    assert CFG["deployment"] == old["deployment"]


def test_the_four_metrics_by_hand():
    read = lambda m, ctx: spec.plugin("layer_metrics", m).read(ctx)
    c = {"devindex.route.f1": 30.0, "devindex.route.fd": 10.0,
         "devindex.program_slot.00": 5.0, "devindex.program_slot.01": 0.0,
         "devindex.program_slot.07": 35.0}
    tr = {"modules": {"jit__two_phase(3)": 0.030, "jit__direct_cube(1)": 0.5}}
    ctx = {"counters": c, "trace": tr, "answers_in_span": 20}
    assert read("f1_share", ctx) == pytest.approx(75.0)
    assert read("window_programs", ctx) == 2.0
    # 20 answers in the span, 75% of them F1: 30 ms over 15 answers
    assert read("f1_wave_device_ms", ctx) == pytest.approx(2.0)
    # a program without the counters (the parent): nothing, never a 0
    bare = {"counters": {"query": 9.0}, "trace": tr, "answers_in_span": 20}
    for m in ("f1_share", "window_programs", "f1_wave_device_ms"):
        assert read(m, bare) is None
    assert read("f1_wave_device_ms", {**ctx, "trace": None}) is None
    from open_source_search_engine_tpu.utils import trace
    from open_source_search_engine_tpu.utils.stats import g_stats
    if "devindex.warm_f1" not in g_stats.snapshot()["latencies"]:
        assert read("warm_f1_s", ctx) is None
    trace.record("devindex.warm_f1", 10.0, 12.5)
    assert read("warm_f1_s", ctx) >= 2.5


def test_the_benchmark_file_gained_entries_and_lost_nothing():
    now = spec.benchmark()
    shown = subprocess.run(
        ["git", "show", "57184a9c1040b53dd1f7328d25034b1027d9fc9a:"
         "BENCHMARK.json"], cwd=spec.REPO, capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here to read the parent's file from")
    was = json.loads(shown.stdout)
    for k in ("command", "paths", "run_seconds", "end_to_end"):
        assert now[k] == was[k]
    for k in ("configs", "workloads", "per_layer"):
        assert now[k][:len(was[k])] == was[k]
    cell = spec.cell("gbshard-text-80k.mix4-c32", now)
    assert cell["chips"] == 1 and cell["config"] == "gbshard-text-80k"
    assert cell["config_entry"]["source"] == CFG["source"]
    new = now["per_layer"][len(was["per_layer"]):]
    assert [m["name"] for m in new] == ["f1_share", "f1_wave_device_ms",
                                        "window_programs", "warm_f1_s"]
    layers = {m["layer"] for m in was["per_layer"]}
    for m in new:
        assert m["layer"] in layers and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    for e in now["configs"] + now["workloads"]:
        assert all(len(e[k]) <= 200 for k in ("source", "why") if k in e)
