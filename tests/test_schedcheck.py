"""Deterministic schedule explorer — arming contract, determinism,
seeded historical-bug regressions, and the five scenario suites.

Two halves:

* **Always-run** (tier-1, armed or not): the jitwatch/lockcheck no-op
  contract — outside an active ``explore()`` every factory returns the
  plain ``threading`` primitive and ``sched_point`` is free; and, from
  an unarmed session, one armed child process that explores the five
  scenario suites and must find no failure.
* **Armed-only** (``OSSE_SCHED=1``, check.sh schedcheck step): the
  explorer itself — byte-identical replay, toy lost-update found and
  shrunk, ABBA deadlock detection, both seeded historical bugs
  (PR 4 generation stamping, PR 13 lone-hog displacement) found within
  a bounded budget, and the five protocol scenario suites clean at
  ``OSSE_SCHED_BUDGET`` schedules.
"""

import functools
import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from open_source_search_engine_tpu.utils import lockcheck, schedcheck, threads

from tests import sched_scenarios

BUDGET = int(os.environ.get("OSSE_SCHED_BUDGET", "64"))
ROOT = Path(__file__).resolve().parent.parent

armed = pytest.mark.skipif(
    not schedcheck.ENABLED,
    reason="schedule exploration needs OSSE_SCHED=1 at import")


# --- the no-op contract (always runs) --------------------------------------


class TestUnarmedNoOp:
    """Outside an active explore() the plane must cost nothing: plain
    primitives, no wrappers, sched_point a no-op — whether or not
    OSSE_SCHED=1 is set (arming alone must not perturb tier-1)."""

    def test_factories_return_plain_primitives_when_idle(self):
        assert schedcheck._active is None
        assert not isinstance(lockcheck.make_lock("t.l"),
                              schedcheck.SchedLock)
        assert not isinstance(lockcheck.make_rlock("t.rl"),
                              schedcheck.SchedRLock)
        assert isinstance(lockcheck.make_condition("t.cv"),
                          threading.Condition)
        assert isinstance(lockcheck.make_event("t.ev"), threading.Event)
        t = threads.make_thread("t.th", lambda: None)
        assert type(t) is threading.Thread

    def test_sched_point_and_settle_are_noops_when_idle(self):
        schedcheck.sched_point("anywhere")
        schedcheck.settle()  # returns immediately, no virtual clock

    def test_explore_requires_arming(self):
        if schedcheck.ENABLED:
            pytest.skip("armed session")
        with pytest.raises(RuntimeError, match="OSSE_SCHED"):
            schedcheck.explore(lambda: None, schedules=1)

    def test_monotonic_unpatched_when_idle(self):
        import time
        assert time.monotonic is schedcheck._REAL_MONOTONIC


    def test_an_armed_process_explores_every_scenario_clean(self):
        """Arming is read at import, so an unarmed session (the driver's
        tier-1 run) explores in a child: every scenario suite, BUDGET
        seeded schedules each, no failure and no idle scenario."""
        if schedcheck.ENABLED:
            pytest.skip("armed session: TestScenarioSuites explores here")
        code = (
            "import json\n"
            "from open_source_search_engine_tpu.utils import schedcheck\n"
            "from tests import sched_scenarios\n"
            f"print(json.dumps({{n: schedcheck.explore(f, schedules={BUDGET})"
            " for n, f in sorted(sched_scenarios.SCENARIOS.items())}))\n")
        env = dict(os.environ, OSSE_SCHED="1", JAX_PLATFORMS="cpu")
        p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]  # a ScheduleFailure's
        # shrunk thread/lock timeline is its message
        out = json.loads(p.stdout.splitlines()[-1])
        assert sorted(out) == sorted(sched_scenarios.SCENARIOS)
        for name, rep in out.items():
            assert rep["failures"] == 0 and rep["schedules"] == BUDGET, name
            assert rep["yield_points"] > 0, name


# --- toy workloads for the explorer itself ---------------------------------


def _toy_lost_update():
    counter = {"v": 0}

    def bump(name):
        v = counter["v"]
        schedcheck.sched_point(f"{name}.read")
        counter["v"] = v + 1

    ts = [threads.make_thread(f"w{i}",
                              functools.partial(bump, f"w{i}"))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counter["v"] == 2, f"lost update: counter={counter['v']}"


def _toy_locked_update():
    counter = {"v": 0}
    mu = lockcheck.make_lock("toy.mu")

    def bump(name):
        with mu:
            v = counter["v"]
            schedcheck.sched_point(f"{name}.read")
            counter["v"] = v + 1

    ts = [threads.make_thread(f"w{i}",
                              functools.partial(bump, f"w{i}"))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counter["v"] == 2


def _toy_abba():
    a = lockcheck.make_lock("toy.A")
    b = lockcheck.make_lock("toy.B")

    def t1():
        with a:
            schedcheck.sched_point("t1.holds.A")
            with b:
                pass

    def t2():
        with b:
            schedcheck.sched_point("t2.holds.B")
            with a:
                pass

    ts = [threads.make_thread("t1", t1), threads.make_thread("t2", t2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()


@armed
class TestExplorer:
    def test_same_seed_byte_identical_trace(self):
        """One seed = one exact interleaving, replayable forever."""
        t1 = schedcheck.trace_of(_toy_lost_update, seed=7)
        t2 = schedcheck.trace_of(_toy_lost_update, seed=7)
        assert t1 == t2
        assert any("sched_point" in ln or ".read" in ln for ln in t1)

    def test_toy_race_found_and_shrunk(self):
        with pytest.raises(schedcheck.ScheduleFailure) as ei:
            schedcheck.explore(_toy_lost_update, schedules=BUDGET)
        f = ei.value
        assert f.schedules_run <= BUDGET
        # shrunk to a minimal preemption trace: one forced switch
        # between the read and the write is sufficient
        assert len(f.decisions) <= 2, f.decisions
        assert ".read" in str(f), "timeline must name the racing point"

    def test_locked_toy_survives_exploration(self):
        out = schedcheck.explore(_toy_locked_update, schedules=32)
        assert out["failures"] == 0
        assert out["yield_points"] > 0

    def test_abba_deadlock_detected(self):
        with pytest.raises(schedcheck.ScheduleFailure) as ei:
            schedcheck.explore(_toy_abba, schedules=BUDGET)
        assert "deadlock" in str(ei.value)

    def test_failure_replay_reproduces(self):
        """The seed in a ScheduleFailure replays to the same failure."""
        with pytest.raises(schedcheck.ScheduleFailure) as ei:
            schedcheck.explore(_toy_lost_update, schedules=BUDGET)
        seed = ei.value.seed
        with pytest.raises(schedcheck.ScheduleFailure) as ei2:
            schedcheck.explore(_toy_lost_update, schedules=1, seed=seed)
        assert ei2.value.seed == seed


# --- the five protocol scenario suites -------------------------------------


@armed
class TestScenarioSuites:
    @pytest.mark.parametrize("name", sorted(sched_scenarios.SCENARIOS))
    def test_scenario_clean_under_budget(self, name):
        fn = sched_scenarios.SCENARIOS[name]
        out = schedcheck.explore(fn, schedules=BUDGET)
        assert out["failures"] == 0
        assert out["schedules"] == BUDGET
        assert out["yield_points"] > 0, "scenario never hit the plane?"


# --- seeded historical-bug regressions -------------------------------------


@armed
class TestSeededRegressions:
    """The explorer must rediscover the races this repo actually
    shipped, from test-local buggy subclasses — within budget, with
    shrunk traces that name the racing points."""

    def test_pr4_generation_stamp_race_found(self):
        # PR 4: cache entry stamped with the generation re-read at put
        # time instead of captured at entry — a write landing between
        # compute and put masquerades the stale value as fresh
        fn = functools.partial(
            sched_scenarios.scenario_cache_generation,
            cache_cls=sched_scenarios.make_buggy_cache_cls())
        with pytest.raises(schedcheck.ScheduleFailure) as ei:
            schedcheck.explore(fn, schedules=BUDGET)
        f = ei.value
        assert f.schedules_run <= BUDGET
        msg = str(f)
        assert "gen.bump" in msg and "buggy.put" in msg, msg

    def test_pr13_lone_hog_displacement_found(self):
        # PR 13: _displace_locked computed the victim's share without
        # counting the displacer — a lone hog's share came out
        # unbounded, so the quiet tenant shed queue_full instead
        fn = functools.partial(
            sched_scenarios.scenario_admission_quota,
            gate_cls=sched_scenarios.make_buggy_gate_cls())
        with pytest.raises(schedcheck.ScheduleFailure) as ei:
            schedcheck.explore(fn, schedules=BUDGET)
        f = ei.value
        assert f.schedules_run <= BUDGET
        assert "queue_full" in str(f)


@armed
@pytest.mark.slow
class TestDeepExploration:
    """The deep run: 1024 schedules per scenario, still zero
    findings."""

    @pytest.mark.parametrize("name", sorted(sched_scenarios.SCENARIOS))
    def test_scenario_clean_deep(self, name):
        out = schedcheck.explore(sched_scenarios.SCENARIOS[name],
                                 schedules=1024)
        assert out["failures"] == 0
