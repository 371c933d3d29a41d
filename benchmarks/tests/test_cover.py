"""The cover: bursts per class, no burst for a rare key, the wider plan, and
the checkout's cover file."""

import json

from deployments.single_chip import key_class, key_cost_class
from lib import cover

FD0 = "_direct_cube:4,4,4,0,128,2048"
FD512 = "_direct_cube:4,4,4,512,128,2048"
FD512_R8 = "_direct_cube:4,4,8,512,128,2048"
FD4096 = "_direct_cube:4,4,4,4096,128,2048"
F1 = "_two_phase:4,2,2,128,256,128"


def test_programs_are_primed_in_one_order_whatever_the_walks():
    a = [[F1], [FD4096], [FD0], [FD512], [FD0]]
    b = [[FD0], [FD512], [F1], [FD0], [FD4096]]
    assert [a[i][0] for i in cover.prime_order(a)[0]] \
        == [b[i][0] for i in cover.prime_order(b)[0]] \
        == [FD0, FD4096, FD512, F1]
    assert cover.prime_order(a) == ([2, 1, 3, 0], [])   # each key's first query


def test_a_key_of_the_lists_tail_is_primed_where_that_is_cheap():
    F1b = "_two_phase:4,2,4,128,256,128"
    dry = [[FD0], [F1], [FD0], [FD512_R8], [F1b], [FD4096]]     # head: 3
    args = (dry, 3, {FD4096}, key_cost_class)
    order, left = cover.prime_order(*args)
    # the head's keys; the tail's fast key; the tail's slow key that the
    # checkout compiled before: primed. The tail's slow key it never met: left
    assert [dry[i][0] for i in order] == [FD0, FD4096, F1, F1b]
    assert left == [FD512_R8]
    assert cover.prime_order(dry, 4, set(), key_cost_class)[1] == [FD4096]


def test_one_burst_per_reached_class_whatever_the_order():
    # 32 walked queries: a random burst of them would hold mostly FD0 and
    # could miss the Lp512 class; the planner forms one burst for each class
    # that five or more walked queries ride
    walk = [[FD0]] * 20 + [[FD512]] * 6 + [[F1]] * 5 + [[FD4096]]
    bursts = cover.plan_bursts(walk, key_class, 5)
    assert [b["class"] for b in bursts] == ["f1:k256:128", "fd:Lp0",
                                            "fd:Lp512"]
    for b in bursts:
        assert len(b["queries"]) == 5
        assert all(walk[i][0] == b["key"] for i in b["queries"])


def test_rare_key_gets_no_burst():
    walk = [[FD0]] * 9 + [[FD4096]] * 4
    assert [b["class"] for b in cover.plan_bursts(walk, key_class, 5)] \
        == ["fd:Lp0"]
    # a class five queries ride, but no one key of it five times: none
    walk = [[FD512]] * 3 + [[FD512_R8]] * 3
    assert cover.plan_bursts(walk, key_class, 5) == []


def test_burst_takes_the_commonest_key_of_its_class():
    walk = [[FD512_R8]] * 5 + [[FD512]] * 7
    (b,) = cover.plan_bursts(walk, key_class, 5)
    assert b["key"] == FD512


def test_burst_prefers_queries_that_rode_no_further_rung():
    up = [F1, "_two_phase:4,2,2,128,2048,512"]      # escalated in the walk
    walk = [up, up, [F1], up, [F1], [F1], [F1], [F1], [F1]]
    (b,) = cover.plan_bursts(walk, key_class, 5)
    assert b["queries"] == [2, 4, 5, 6, 7]
    walk = [up, up, [F1], up, [F1], [F1], up]       # too few: filled up
    (b,) = cover.plan_bursts(walk, key_class, 5)
    assert b["queries"] == [2, 4, 5, 0, 1]


def test_wider_plan_finds_what_the_walk_missed_and_caps_slow_programs():
    have = {FD0, F1}
    dry = [[FD0], [FD4096], [FD512], [FD512], [FD512_R8], [F1],
           ["_two_phase:4,2,2,512,256,128"]]
    wide = cover.plan_wide(dry, have, key_cost_class, {"slow": 2, "fast": 8})
    assert wide[0]["key"] == FD512 and wide[0]["n"] == 2
    assert wide[0]["query"] == 2
    slow = [w for w in wide if w["key"].startswith("_direct_cube")]
    assert [w["skipped"] for w in slow] == [False, False, True]
    assert not [w for w in wide if w["key"] in have]


def test_cover_file_reports_and_adds_a_missing_key(tmp_path):
    path = tmp_path / "cover" / "cell.json"
    had, new = cover.check_file(path, {FD0: {"first_s": 90.0, "n": 3}})
    assert not had and [k["key"] for k in new] == [FD0]
    had, new = cover.check_file(path, {FD0: {"first_s": 0.1, "n": 3}})
    assert had and new == []
    had, new = cover.check_file(
        path, {FD0: {"first_s": 0.1, "n": 3},
               FD512: {"first_s": 93.2, "n": 1}})
    assert had and new == [{"key": FD512, "cost_s": 93.2}]
    assert json.loads(path.read_text())["keys"] == sorted([FD0, FD512])
