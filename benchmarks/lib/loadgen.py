"""The load generator: a process of its own that never imports jax.

    python3 loadgen.py --port P --plan plan.json --out results.json

``plan.json``: {"queries": [...], "loop": "closed"|"open", "clients": n |
"starts"/"due": [...], "lead_in": s, "seconds": s, "path": "/search?...q=",
"drain_s": s, "timeout_s": s}. It prints ``ready``, waits for ``go`` on its
standard input, sends for lead_in + seconds, lets what is in flight come home
(every wait with a limit), writes the results, prints ``done``.

Every request is kept: when it was due, sent and answered (seconds after
"go", this process's clock), its status and the answer's body. The parent
reduces them; nothing is judged here. A sender that finds the list of queries
at its end stops, and ``ran_out`` says so: the parent holds such a window not
correct, since its rate was capped by the list.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
import urllib.parse


GO_LIMIT_S = 120.0      # how long "go" may take to come, once "ready" is said


def fetch(port: int, path: str, timeout: float) -> tuple[int, str]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read().decode("utf-8", "replace")
    finally:
        conn.close()


class Gen:
    def __init__(self, port: int, plan: dict):
        self.port, self.plan = port, plan
        self.queries = plan["queries"]
        self.rows: list[dict] = []
        self.lock = threading.Lock()
        self.next_q = 0
        self.ran_out = False
        self.t_go = 0.0
        self.end = plan["lead_in"] + plan["seconds"]

    def now(self) -> float:
        return time.perf_counter() - self.t_go

    def take(self) -> int | None:
        with self.lock:
            if self.next_q >= len(self.queries):
                self.ran_out = True
                return None
            self.next_q += 1
            return self.next_q - 1

    def one(self, qi: int, due: float) -> None:
        path = self.plan["path"] + urllib.parse.quote(self.queries[qi])
        sent = self.now()
        try:
            status, body = fetch(self.port, path, self.plan["timeout_s"])
            err = None
        except Exception as e:  # noqa: BLE001 -- a failed request is a row
            status, body, err = 0, "", repr(e)
        row = {"q": qi, "due": due, "sent": sent, "done": self.now(),
               "status": status, "body": body}
        if err:
            row["error"] = err
        with self.lock:
            self.rows.append(row)

    def closed_client(self, start: float) -> None:
        while self.now() < start:
            time.sleep(min(0.005, max(start - self.now(), 0.0)))
        while self.now() < self.end:
            qi = self.take()
            if qi is None:
                return
            self.one(qi, self.now())

    def open_worker(self, dues: list[float]) -> None:
        while True:
            with self.lock:
                if not dues:
                    return
                due = dues.pop(0)
            while self.now() < due:
                time.sleep(min(0.002, max(due - self.now(), 0.0)))
            qi = self.take()
            if qi is None:
                return
            self.one(qi, due)

    def run(self) -> list[threading.Thread]:
        p = self.plan
        if p["loop"] == "closed":
            ts = [threading.Thread(target=self.closed_client, args=(s,),
                                   name=f"client-{k}", daemon=True)
                  for k, s in enumerate(p["starts"])]
        else:
            dues = list(p["due"])
            ts = [threading.Thread(target=self.open_worker, args=(dues,),
                                   name=f"worker-{k}", daemon=True)
                  for k in range(p["workers"])]
        self.t_go = time.perf_counter()
        for t in ts:
            t.start()
        return ts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    with open(a.plan, encoding="utf-8") as f:
        plan = json.load(f)
    gen = Gen(a.port, plan)
    print("ready", flush=True)
    said: list[str] = []
    waiter = threading.Thread(
        target=lambda: said.append(sys.stdin.readline().strip()),
        name="wait-for-go", daemon=True)
    waiter.start()
    waiter.join(timeout=GO_LIMIT_S)
    if said != ["go"]:
        return 2
    threads = gen.run()
    limit = gen.end + plan["drain_s"]
    for t in threads:
        t.join(timeout=max(limit - gen.now(), 0.1))
    left = [t.name for t in threads if t.is_alive()]
    with gen.lock:
        rows = list(gen.rows)
    with open(a.out, "w", encoding="utf-8") as f:
        json.dump({"rows": rows, "never_ended": left,
                   "ran_out": gen.ran_out, "closed_at": gen.now()}, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
