"""Every wait has a limit, a stall says where it stood, and after the last
line the process is gone."""

import json
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

STALL = """
import sys, time
sys.path.insert(0, {bench!r})
from lib import watchdog
run = watchdog.Run(label="t-stall")
run.last_line = {{"device": {{"platform": "cpu"}}}}
run.phase("waiting")
p = run.spawn([sys.executable, "-c", "import time; time.sleep(600)"],
              stdout=-1, text=True)
{wait}
"""


def _script(wait: str) -> subprocess.CompletedProcess:
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, "-c",
                        STALL.format(bench=str(BENCH), wait=wait)],
                       capture_output=True, text=True, timeout=60)
    p.elapsed = time.perf_counter() - t0
    return p


def test_child_that_never_answers_ends_the_run_with_stacks_and_a_line():
    p = _script('run.read_line(p, 1.0, "child ready")')
    assert p.returncode == 4 and p.elapsed < 10
    assert "STALL in phase 'waiting'" in p.stderr
    assert "--- thread MainThread" in p.stderr
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["stall"]["phase"] == "waiting"
    assert line["device"] == {"platform": "cpu"}
    assert list((BENCH / "_work" / "stalls").glob("t-stall-*"))


def test_thread_that_never_ends_and_the_runs_own_limit():
    p = _script('import threading\n'
                't = threading.Thread(target=time.sleep, args=(600,), '
                'daemon=True); t.start()\n'
                'run.join(t, 1.0, "client thread")')
    assert p.returncode == 4 and "client thread" in p.stderr
    p = _script('run.set_deadline(1.0); time.sleep(30)')
    assert p.returncode == 4 and p.elapsed < 10
    assert "the run's own limit" in p.stderr


def test_gone_within_5s_of_the_last_line_with_a_child_that_ignores_sigterm():
    code = STALL.format(bench=str(BENCH), wait="") .replace(
        '"import time; time.sleep(600)"',
        '"import signal, time; signal.signal(signal.SIGTERM, '
        'signal.SIG_IGN); print(1, flush=True); time.sleep(600)"') + \
        'p.stdout.readline()\nrun.finish({"correct": True}, 0)\n'
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    t_line = time.perf_counter()
    assert json.loads(line) == {"correct": True}
    assert proc.wait(timeout=5.0) == 0
    assert time.perf_counter() - t_line < 5.0


def test_load_generator_ends_inside_its_limits_against_a_mute_server(tmp_path):
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    held = []
    threading.Thread(target=lambda: [held.append(srv.accept())
                                     for _ in range(64)],
                     daemon=True).start()
    plan = {"queries": [f"word{i}" for i in range(50)], "loop": "closed",
            "starts": [0.0, 0.1], "lead_in": 0.2, "seconds": 0.5,
            "path": "/search?q=", "drain_s": 1.0, "timeout_s": 1.0}
    (tmp_path / "plan.json").write_text(json.dumps(plan))
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(BENCH / "lib" / "loadgen.py"), "--port",
         str(srv.getsockname()[1]), "--plan", str(tmp_path / "plan.json"),
         "--out", str(tmp_path / "rows.json")],
        input="go\n", capture_output=True, text=True, timeout=30)
    assert p.returncode == 0 and time.perf_counter() - t0 < 10
    rows = json.loads((tmp_path / "rows.json").read_text())["rows"]
    assert rows and all(r["status"] == 0 and "error" in r for r in rows)
