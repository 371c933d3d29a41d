"""Whole runs on the CPU at a tiny size (slow: ~1 min each): a sound run is
correct, every fault the cell can have comes out not correct, the control
fails, the reference agrees with the program's host flat path, and a cell, a
configuration, a mix and a metric are added as files only."""

import json
import shutil
import subprocess
import sys

import numpy as np
import pytest

from _helpers import BENCH, drive
from corpora import zipf_html
from lib import compare, spec
from reference.gb_minscore import Reference

ARGS = ("--workload", "gbshard-80k.mix-c32", "--seconds", "2", "--trace",
        "0", "--rehearse-docs", "400")


def test_sound_run_is_correct_and_walked_all_it_sent(sound_run):
    assert sound_run["correct"] is True and sound_run["failed"] == 0
    assert set(sound_run["metrics"]) == {"qps", "p50_ms", "p95_ms", "setup_s"}
    assert [k for k in sound_run if k[0] != "_"][-1] == "compared"
    assert all(v <= lim for v, lim in sound_run["compared"].values())
    assert '"phase": "cover"' in sound_run["_stderr"]
    assert '"window_compiles": 0.0' in sound_run["_stderr"]


@pytest.mark.parametrize("fault,number", [
    ("altered_answer", "ladder_gap"), ("host_path", "off_device"),
    ("wrong_total", "total_gap"), ("compile_in_window", "window_compiles")])
def test_fault_comes_out_not_correct(fault, number):
    line = drive(*ARGS, "--seed", "78", fault=fault)
    assert line["correct"] is False
    value, limit = line["compared"][number]
    assert value > limit


def test_a_window_that_outruns_its_list_is_not_correct(tmp_path):
    """The yardstick's own ceiling is loud: a list sized for 1 query a second
    ends inside the window, the senders stop, and the run is not correct."""
    mix = spec.load_json(BENCH / "traffic" / "mix-c32.json")
    mix["walk"].update(rate_cap=1, list_rate=1, in_flight=4)
    mix["http_pass"] = 2
    path = BENCH / "traffic" / "t-short-list.json"
    path.write_text(json.dumps(mix))
    try:
        bench = spec.benchmark()
        bench["workloads"].append({**bench["workloads"][0],
                                   "name": "t.short-list",
                                   "traffic": "t-short-list"})
        (tmp_path / "B.json").write_text(json.dumps(bench))
        line = drive("--workload", "t.short-list", "--seed", "80",
                     "--seconds", "2", "--trace", "0", "--rehearse-docs",
                     "400", f"--bench={tmp_path / 'B.json'}")
        assert line["correct"] is False
        assert line["compared"]["list_ran_out"] == [1.0, 0]
        assert line["attempted"] <= 1 * (3 + 2) + 4
    finally:
        path.unlink(missing_ok=True)
        (BENCH / "_work" / "cover" / "t.short-list.json").unlink(
            missing_ok=True)


def test_control_fails_and_reference_agrees_with_the_host_flat_path():
    p = subprocess.run([sys.executable, str(BENCH / "tools" / "control.py"),
                        "--self-test", "600"], capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["host_flat_path"]["mismatches"] == 0
    assert out["host_flat_path"]["queries"] >= 60
    for name, c in out["controls"].items():
        assert c["correct"] is False, name


def test_a_cell_is_added_as_files_only(tmp_path):
    """One of each, as files and entries only: a configuration, a traffic
    mix, a per-layer metric, and the cell that uses them."""
    made = []
    try:
        cfg = spec.load_json(BENCH / "configs" / "gbshard-80k.json")
        cfg["name"] = "t-disc"
        (BENCH / "configs" / "t-disc.json").write_text(json.dumps(cfg))
        made.append(BENCH / "configs" / "t-disc.json")
        mix = spec.load_json(BENCH / "traffic" / "mix-open16.json")
        mix["rate"] = 6
        (BENCH / "traffic" / "t-open6.json").write_text(json.dumps(mix))
        made.append(BENCH / "traffic" / "t-open6.json")
        (BENCH / "layer_metrics" / "t_answers.py").write_text(
            "def read(ctx):\n    return float(ctx['answers_in_span'])\n")
        made.append(BENCH / "layer_metrics" / "t_answers.py")
        bench = spec.benchmark()
        bench["configs"].append({**bench["configs"][0], "name": "t-disc",
                                 "file": "benchmarks/configs/t-disc.json"})
        bench["workloads"].append({"name": "t-disc.t-open6",
                                   "config": "t-disc", "traffic": "t-open6",
                                   "chips": 1, "why": "test"})
        bench["per_layer"].append({
            "name": "t_answers", "unit": "answers", "better": "higher",
            "source": "host_clock", "layer": "front door", "moves": "qps",
            "workloads": ["t-disc.t-open6"]})
        (tmp_path / "B.json").write_text(json.dumps(bench))
        line = drive("--workload", "t-disc.t-open6", "--seed", "79",
                     "--seconds", "2", "--trace", "1", "--rehearse-docs",
                     "400", f"--bench={tmp_path / 'B.json'}")
        assert line["correct"] is True
        assert line["attempted"] == 12          # 6 a second for 2 s, exactly
        assert line["metrics"]["t_answers"]["value"] > 0
        assert "batch_fill" in line["metrics"]
        # ... and the new metric is not asked of the cells that do not list it
        assert [m["name"] for m in spec.metrics_of(
            bench, "gbshard-80k.mix-c32", "per_layer")].count("t_answers") == 0
    finally:
        for p in made:
            p.unlink(missing_ok=True)
        shutil.rmtree(BENCH / "_work" / "cover" / "t-disc.t-open6.json",
                      ignore_errors=True)
        (BENCH / "_work" / "cover" / "t-disc.t-open6.json").unlink(
            missing_ok=True)
