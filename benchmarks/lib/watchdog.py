"""Phases, a heartbeat, waits with a limit, and the forced exit.

Every wait of the benchmark goes through this file, so that none is without a
limit. A wait that passes its limit is a *stall*: every thread's stack and the
phase go to stderr and to ``_work/stalls/``, the children are killed, the
contract's last line is printed with ``correct`` false, and the process ends
with a non-zero code. After the last line the process is gone at once
(``os._exit``): neither the chip runtime's teardown nor a child can hold it.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
import traceback

from . import spec

HEARTBEAT_S = 10.0
STALL_CODE = 4


class Run:
    """One run's clock, phase, children and last line."""

    def __init__(self, label: str = "run", t0: float | None = None,
                 out=None, err=None, hard_exit: bool = True):
        self.t0 = time.perf_counter() if t0 is None else t0
        self.label = label
        self.phase_name = "start"
        self.children: list[subprocess.Popen] = []
        self.out = out or sys.stdout
        self.err = err or sys.stderr
        self.hard_exit = hard_exit
        self.last_line: dict | None = None   # what a stall still owes stdout
        self.final_line: dict | None = None  # the result, kept for tests
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._beat: threading.Thread | None = None
        self._deadline: threading.Timer | None = None
        self.exit_code: int | None = None

    # ----------------------------------------------------------- reporting
    def at(self) -> float:
        return round(time.perf_counter() - self.t0, 3)

    def say(self, **kw) -> None:
        """One JSON line on stderr."""
        with self._lock:
            print(json.dumps({"at_s": self.at(), **kw}, default=str),
                  file=self.err, flush=True)

    def phase(self, name: str, **kw) -> None:
        self.phase_name = name
        self.say(phase=name, **kw)

    def start_heartbeat(self) -> None:
        def beat():
            while not self._stop.wait(HEARTBEAT_S):
                self.say(heartbeat=self.phase_name)
        self._beat = threading.Thread(target=beat, name="bench-heartbeat",
                                      daemon=True)
        self._beat.start()

    def set_deadline(self, seconds: float) -> None:
        """The whole run's limit: past it the run is a stall."""
        if self._deadline is not None:
            self._deadline.cancel()
        self._deadline = threading.Timer(
            max(seconds - (time.perf_counter() - self.t0), 0.1),
            lambda: self.stall(f"the run's own limit of {seconds:.0f} s"))
        self._deadline.daemon = True
        self._deadline.start()

    # ------------------------------------------------------------ children
    def spawn(self, cmd: list[str], **kw) -> subprocess.Popen:
        """Start a child by exec (never a fork of this process's memory)."""
        p = subprocess.Popen(cmd, **kw)
        self.children.append(p)
        return p

    def kill_children(self) -> None:
        for p in self.children:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGKILL)
                except OSError:
                    pass
        for p in self.children:
            try:
                p.wait(timeout=2.0)
            except (subprocess.TimeoutExpired, OSError):
                pass

    # --------------------------------------------------------- bounded waits
    def wait_proc(self, p: subprocess.Popen, limit: float, what: str) -> int:
        try:
            return p.wait(timeout=limit)
        except subprocess.TimeoutExpired:
            self.stall(f"{what}: child {p.pid} not done in {limit:.0f} s")
            raise

    def join(self, t: threading.Thread, limit: float, what: str) -> None:
        t.join(timeout=limit)
        if t.is_alive():
            self.stall(f"{what}: thread {t.name} not done in {limit:.0f} s")

    def get(self, q: "queue.Queue", limit: float, what: str):
        try:
            return q.get(timeout=limit)
        except queue.Empty:
            self.stall(f"{what}: nothing in {limit:.0f} s")
            raise

    def read_line(self, p: subprocess.Popen, limit: float, what: str) -> str:
        """One line of a child's stdout, or a stall."""
        box: "queue.Queue[str]" = queue.Queue()
        t = threading.Thread(target=lambda: box.put(p.stdout.readline()),
                             name=f"read-{what}", daemon=True)
        t.start()
        line = self.get(box, limit, what)
        if not line:
            self.stall(f"{what}: child {p.pid} closed its output "
                       f"(exit {p.poll()})")
        return line.strip()

    # ------------------------------------------------------------ the ends
    def stacks(self) -> str:
        names = {t.ident: t.name for t in threading.enumerate()}
        parts = []
        for ident, frame in sys._current_frames().items():
            parts.append(f"--- thread {names.get(ident, ident)}\n"
                         + "".join(traceback.format_stack(frame)))
        return "\n".join(parts)

    def stall(self, why: str) -> None:
        text = (f"STALL in phase {self.phase_name!r} at {self.at()} s: {why}\n"
                + self.stacks())
        try:
            d = spec.WORK / "stalls"
            d.mkdir(parents=True, exist_ok=True)
            (d / f"{self.label}-{int(time.time())}-{os.getpid()}.txt"
             ).write_text(text)
        except OSError:
            pass
        print(text, file=self.err, flush=True)
        line = dict(self.last_line or {})
        line.update(correct=False)
        line.setdefault("attempted", 0)
        line.setdefault("failed", 0)
        line.setdefault("metrics", {})
        line.setdefault("device", {})
        line["stall"] = {"phase": self.phase_name, "why": why}
        self.finish(line, STALL_CODE)

    def finish(self, line: dict | None, code: int) -> None:
        """Print the last line (if any), kill what is left, and go."""
        self._stop.set()
        if self._deadline is not None:
            self._deadline.cancel()
        if line is not None:
            with self._lock:
                print(json.dumps(line), file=self.out, flush=True)
        self.kill_children()
        self.exit_code = code
        if self.hard_exit:
            try:
                self.out.flush()
                self.err.flush()
            finally:
                os._exit(code)
        raise SystemExit(code)
