"""Auxiliary subsystems: DailyMerge scheduler + sampling profiler."""

import time
from datetime import datetime

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.control.dailymerge import (DailyMerge,
                                                              in_window,
                                                              parse_window)
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.utils.parms import Conf
from open_source_search_engine_tpu.utils.profiler import SamplingProfiler


def test_window_parsing():
    assert parse_window("2-5") == (2, 5)
    assert parse_window("22-4") == (22, 4)
    assert parse_window("") is None and parse_window("x") is None
    assert in_window(3, (2, 5)) and not in_window(6, (2, 5))
    assert in_window(23, (22, 4)) and in_window(1, (22, 4))
    assert not in_window(12, (22, 4))


def test_daily_merge_sweeps_once_per_day(tmp_path):
    c = Collection("dm", tmp_path)
    for i in range(4):  # several runs so a forced merge has work
        docproc.index_document(c, f"http://dm.test/d{i}",
                               f"<html><body><p>merge words "
                               f"number{i}</p></body></html>")
        c.posdb.dump()
    assert len(c.posdb.runs) >= 2
    conf = Conf()
    conf.merge_quiet_hours = "0-24"  # malformed (24) -> disabled
    dm = DailyMerge([c], conf)
    assert not dm.tick()
    conf.merge_quiet_hours = "2-5"
    assert dm.tick(now=datetime(2026, 7, 30, 3, 0)) is True
    assert len(c.posdb.runs) == 1          # fully merged
    # same day, still in window: no second sweep
    assert dm.tick(now=datetime(2026, 7, 30, 4, 0)) is False
    # next day: sweeps again
    assert dm.tick(now=datetime(2026, 7, 31, 2, 30)) is True


def test_sampling_profiler_catches_hot_function():
    prof = SamplingProfiler(interval_s=0.002)

    def hot_spin(deadline):
        x = 0
        while time.perf_counter() < deadline:
            x += 1
        return x

    prof.start()
    hot_spin(time.perf_counter() + 0.4)
    prof.stop()
    # by hits, not by rank: a worker that has run other files holds
    # dozens of parked threads, each sampled on every tick, whose frames
    # tie with the busy thread's and can crowd it out of the top 30
    rep = prof.report(top=100_000)
    assert rep["samples"] > 20

    def hits(rows, func):
        return sum(r["hits"] for r in rows if r["func"] == func)
    # this test's own frame is on the main thread's stack at every tick
    ticks = hits(rep["top_cumulative"],
                 "test_sampling_profiler_catches_hot_function")
    assert ticks > 20
    assert hits(rep["top_self"], "hot_spin") >= 0.5 * ticks
    assert hits(rep["top_cumulative"], "hot_spin") >= 0.5 * ticks
    prof.reset()
    assert prof.report()["samples"] == 0
