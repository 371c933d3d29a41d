"""The yardstick's own arithmetic: the open loop's schedule, latencies from
due, needed bytes by hand, the trace reduction, the walk count."""

from types import SimpleNamespace as NS

import numpy as np
import pytest

from corpora import zipf_html
from lib import measure, runner, schedule, spec, trace_reduce

P = {"vocab": 2000, "zipf_a": 1.35, "min_words": 60, "max_words": 219,
     "sites": 97, "sentence_words": 12, "title_words": 4}


@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 + 11, 987654321])
def test_open_loop_holds_exactly_rate_times_seconds_arrivals(seed):
    due = schedule.open_loop(seed, 16, 3, 20)
    assert len(due) == 16 * 23 and due == sorted(due)
    assert sum(1 for t in due if t < 3) == 48
    assert sum(1 for t in due if 3 <= t < 23) == 320
    assert due != schedule.open_loop(seed + 1, 16, 3, 20)
    assert due == schedule.open_loop(seed, 16, 3, 20)


def test_open_loop_latency_counts_from_due_and_failures_are_slowest():
    lat = spec.plugin("end_to_end", "_latency")
    rows = [{"due": 1.0, "sent": 1.4, "done": 1.5, "status": 200},
            {"due": 1.1, "sent": 1.5, "done": 1.7, "status": 503},
            {"due": 0.5, "sent": 0.5, "done": 1.2, "status": 200}]
    win = {"rows": rows, "open": 1.0, "close": 2.0, "loop": "open",
           "timeout_s": 60.0, "seconds": 1.0}
    assert lat.latencies_ms(win) == [pytest.approx(500.0), 60000.0]
    win["loop"] = "closed"
    assert lat.latencies_ms(win)[0] == pytest.approx(100.0)
    assert spec.plugin("end_to_end", "qps").read(win) == 2.0


def test_needed_bytes_by_hand():
    per_word = np.zeros(2000, int)
    per_word[[1, 2, 3]] = [1000, 50, 7]
    # "word1 word2": (1000 + 50) postings x 8 B + 10 results x 8 B
    assert measure.needed_bytes(["word1 word2"], per_word, 10) == 8480
    # a word twice in a query reads its list once
    assert measure.needed_bytes(["word3 word3"], per_word, 10) == 7 * 8 + 80


def test_postings_per_word_counts_body_and_title():
    lens, ids = zipf_html.word_ids(5, 0, 50, P)
    per = zipf_html.postings_per_word(lens, ids, P)
    assert per.sum() == lens.sum() + 4 * 50
    start = np.concatenate([[0], np.cumsum(lens)])[:-1]
    w = int(ids[0])
    by_hand = int((ids == w).sum()) + sum(
        int((ids[s:s + 4] == w).sum()) for s in start)
    assert per[w] == by_hand


def test_corpus_slices_are_the_same_pages():
    whole = list(zipf_html.pages(9, 0, 2500, P))
    part = list(zipf_html.pages(9, 1990, 2010, P))
    assert part == whole[1990:2010]
    assert zipf_html.doc_of_url(part[0][0]) == 1990
    assert zipf_html.doc_of_url("http://x/other") is None


def test_queries_come_from_the_seed_and_are_unique():
    from queries import zipf_terms
    qp = {"max_terms": 3, "zipf_a": 1.3, "vocab": 2000}
    a, b = zipf_terms.make(1, 600, qp), zipf_terms.make(2**31 + 5, 600, qp)
    assert a != b and len(set(a)) == 600 and len(set(b)) == 600
    assert a == zipf_terms.make(1, 600, qp)
    assert zipf_terms.make(1, 900, qp)[:600] == a   # a longer list starts so
    assert {len(q.split()) for q in a} == {1, 2, 3}


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_trace_reduction_busy_modules_gaps_and_cut():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit__direct_cube(1)", 0, 400),
                                       _ev("jit__two_phase(2)", 600, 100)]),
        NS(name="XLA Ops", events=[_ev("fusion.1", 0, 300),
                                   _ev("reshape.2", 250, 150),
                                   _ev("fusion.1", 600, 100),
                                   _ev("late", 5_000_000_000, 100)])])
    host = NS(name="/host:CPU", lines=[NS(name="t", events=[
        _ev("outer", 0, 1000), _ev("device_get", 420, 150)])])
    tr = trace_reduce.reduce("", span_s=1e-6, planes=[dev, host])
    assert tr["busy_s"] == pytest.approx(500e-9)     # 0-400 and 600-700
    assert tr["window_s"] == 1e-6
    assert dict(map(tuple, tr["ops"]))["fusion.1"] == pytest.approx(400e-9)
    assert "late" not in dict(map(tuple, tr["ops"]))
    assert tr["modules"]["jit__direct_cube(1)"] == pytest.approx(400e-9)
    assert tr["gaps"] == [["device_get", pytest.approx(200e-9)]]
    ctx = {"trace": tr, "is_wave": lambda n: "_direct_cube" in n
           or "_two_phase" in n, "answers_in_span": 2,
           "needed_bytes": 819, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert spec.plugin("layer_metrics", "wave_device_ms").read(ctx) \
        == pytest.approx(1000 * 500e-9 / 2)
    assert spec.plugin("layer_metrics", "wave_hbm_roofline").read(ctx) \
        == pytest.approx(100 * 1e-9 / 500e-9)
    assert spec.plugin("layer_metrics", "device_idle_share").read(ctx) \
        == pytest.approx(50.0)
    # nothing to read: nothing returned, never a 0
    empty = {"trace": None, "is_wave": lambda n: True}
    for m in ("wave_device_ms", "wave_hbm_roofline", "device_idle_share"):
        assert spec.plugin("layer_metrics", m).read(empty) is None


def test_walk_covers_what_the_window_can_send():
    mix = spec.load_json(spec.BENCH / "traffic" / "mix-c32.json")
    assert runner.walk_count(mix, 20) == 28 * 23 + 32
    # the list: planned on the host and primed, several times what is walked
    assert runner.walk_count(mix, 20, "list_rate") == 120 * 23 + 32
