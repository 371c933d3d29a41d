"""The readers of the request's timeline (PR 28), each by hand on a made-up
``ctx`` and ``None`` on an empty one: what a program without the spans gives."""

import pytest

from _helpers import BENCH  # noqa: F401 -- puts benchmarks/ on the path
from lib import spec

EMPTY = {"counters": {}, "win": {"rows": [], "open": 3.0, "close": 23.0,
                                 "seconds": 20.0, "loop": "closed",
                                 "timeout_s": 30.0}}


def span(name: str, count: float, total_ms: float) -> dict:
    return {f"{name}.count": count, f"{name}.total_ms": total_ms}


def row(sent: float, done: float, status: int = 200) -> dict:
    return {"q": 0, "due": sent, "sent": sent, "done": done,
            "status": status, "body": ""}


def read(name: str, ctx: dict):
    return spec.plugin("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("metric, name", [
    ("gate_wait_ms", "admission.queue_delay"),
    ("unaccounted_ms", "serve.unaccounted"),
    ("batch_wait_ms", "batcher.queue_wait"),
    ("pool_wait_ms", "batcher.pool_wait"),
    ("loop_wait_ms", "resident.queue_wait"),
    ("issue_ms", "resident.issue_wave"),
    ("lock_wait_ms", "query.lock_wait"),
])
def test_a_span_mean_is_total_over_count(metric, name):
    ctx = {**EMPTY, "counters": {**span(name, 8, 100.0),
                                 **span("some.other", 2, 999.0)}}
    assert read(metric, ctx) == 12.5
    assert read(metric, EMPTY) is None
    assert read(metric, {**EMPTY, "counters": span(name, 0, 0.0)}) is None


def test_traced_qps_counts_good_answers_that_arrived_in_the_window():
    rows = [row(1.0, 2.9),              # arrived before the window opened
            row(2.0, 3.0), row(21.0, 22.99),
            row(4.0, 5.0, status=503),  # shed: not a good answer
            row(22.0, 23.0)]            # arrived as it closed: outside
    win = {**EMPTY["win"], "rows": rows}
    assert read("traced_qps", {"counters": {}, "win": win}) == 2 / 20.0
    assert read("traced_qps", {"counters": {}, "win": {**win, "seconds": 0}}
                ) is None


def test_front_unseen_is_client_mean_less_serve_request_mean():
    rows = [row(3.0, 4.5), row(10.0, 11.7),
            row(12.0, 12.2, status=0),  # failed: not in the mean
            row(22.5, 23.5)]            # arrived after the close: outside
    win = {**EMPTY["win"], "rows": rows}
    ctx = {"counters": span("serve.request", 2, 3000.0), "win": win}
    assert read("front_unseen_ms", ctx) == pytest.approx(1600.0 - 1500.0)
    assert read("front_unseen_ms", {"counters": {}, "win": win}) is None
    assert read("front_unseen_ms", {**ctx, "win": EMPTY["win"]}) is None
    # open loop: the client's latency counts from when a request was due
    late = [{**row(5.0, 6.0), "due": 4.0}]
    ctx = {"counters": span("serve.request", 1, 900.0),
           "win": {**win, "rows": late, "loop": "open"}}
    assert read("front_unseen_ms", ctx) == pytest.approx(2000.0 - 900.0)


def test_issue_overlap_is_overlapped_issues_of_all():
    c = {"resident.issue": 40, "resident.issue_overlapped": 10,
         **span("resident.issue_wave", 40, 400.0)}
    assert read("issue_overlap", {**EMPTY, "counters": c}) == 25.0
    del c["resident.issue_overlapped"]          # never overlapped: 0, not None
    assert read("issue_overlap", {**EMPTY, "counters": c}) == 0.0
    # the parent counts its issues and has no span of the loop's: nothing
    assert read("issue_overlap",
                {**EMPTY, "counters": {"resident.issue": 40}}) is None
    assert read("issue_overlap", EMPTY) is None


def test_loop_idle_share_is_idle_ms_of_the_windows_ms():
    c = {**span("resident.idle", 7, 5000.0),
         **span("resident.issue_wave", 40, 400.0)}
    assert read("loop_idle_share", {**EMPTY, "counters": c}) == 25.0
    busy = span("resident.issue_wave", 40, 400.0)   # never starved: 0
    assert read("loop_idle_share", {**EMPTY, "counters": busy}) == 0.0
    assert read("loop_idle_share", EMPTY) is None


def test_stages_sum_is_each_stages_total_over_its_carriers_count():
    c = {**span("serve.request", 4, 4000.0),         # 4 requests,
         **span("batcher.pool_wait", 2, 1600.0),     # 2 batches: 800
         **span("query.lock_wait", 4, 40.0),         # twice a batch: 20
         **span("query.results_work", 2, 60.0),      # 30
         **span("resident.issue_wave", 1, 9.0),      # 1 wave: 9
         **span("resident.collect_wave", 1, 50.0),   # 50
         **span("batcher.queue_wait", 4, 12.0),      # a request: 3
         **span("serve.unaccounted", 4, 8.0),        # 2
         **span("query.results_batch", 2, 999.0)}    # a container: not summed
    assert read("stages_sum_ms", {**EMPTY, "counters": c}) == pytest.approx(
        800 + 20 + 30 + 9 + 50 + 3 + 2)
    # a program without ``serve.request`` (PR 28's parent) keeps no ledger
    assert read("stages_sum_ms", {**EMPTY, "counters": span(
        "admission.queue_delay", 4, 1.0)}) is None
    assert read("stages_sum_ms", EMPTY) is None


def test_stages_sum_reads_every_stage_of_the_program_once():
    from open_source_search_engine_tpu.utils import trace
    carried = spec.plugin("layer_metrics", "stages_sum_ms").CARRIED
    stages = [s for group in carried.values() for s in group]
    assert sorted(stages) == sorted(trace.REQUEST_STAGES)


def test_every_new_metric_is_listed_for_the_cell_and_has_its_file():
    bench = spec.benchmark()
    new = ["traced_qps", "front_unseen_ms", "gate_wait_ms", "unaccounted_ms",
           "batch_wait_ms", "pool_wait_ms", "loop_wait_ms", "issue_ms",
           "issue_overlap", "loop_idle_share", "lock_wait_ms",
           "stages_sum_ms"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(new):] == new
    for name in new:
        assert listed[name]["workloads"] == ["gbshard-80k.mix-c32"]
        assert callable(spec.plugin("layer_metrics", name).read)
