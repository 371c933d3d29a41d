"""One run of one cell, from process start to the forced exit.

Set-up (all of it counted in ``setup_s``): corpus (kept, or indexed by
children while this process reaches the chip) -> server and device base ->
the window's own query list planned on the host and its programs primed ->
walk of the list's head through the direct entry -> bursts and, in the cell's
first run in a checkout, the wider plan (lib/cover.py) -> a short pass over
HTTP -> the load generator started -> caches flushed, set-up's wrapper off. Then the
window, the drain, the memory reading, and only then the reference and the
comparison (lib/compare.py). No list of cells and no ``if`` on a name lives
here: see lib/spec.py.
"""

from __future__ import annotations

import argparse
import json
import math
import queue
import shutil
import sys
import threading
import time
import urllib.parse
import urllib.request

from . import compare, cover, measure, schedule, spec, trace_reduce, watchdog

NO_CHIP = 3          # no accelerator, or fewer chips than the cell asks for
NO_PROGRAM = 2       # nothing to measure in this directory
FIRST_RUN_LIMIT_S = 1150.0      # the check allows a cell's first run 1200 s
RUN_LIMIT_S = 345.0             # ... and every other run 360 s
TRACE_SPAN_S = 5.0      # a traced run traces the window's last seconds only


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-docs", type=int, default=0,
                    help="CPU rehearsal at this many pages: every stage "
                         "runs, no result is printed, exit 3")
    return ap.parse_args(argv)


def window_plan(mix: dict, seed: int, seconds: float, queries: list[str]
                ) -> dict:
    lead = float(mix["lead_in_s"])
    plan = {"queries": queries, "loop": mix["loop"], "lead_in": lead,
            "seconds": seconds, "path": mix["path"],
            "drain_s": float(mix["drain_s"]),
            "timeout_s": float(mix["timeout_s"])}
    if mix["loop"] == "closed":
        plan["starts"] = schedule.closed_loop_starts(int(mix["clients"]), lead)
    else:
        plan["due"] = schedule.open_loop(seed, float(mix["rate"]), lead,
                                         seconds)
        plan["workers"] = int(mix["workers"])
    return plan


def walk_count(mix: dict, seconds: float, rate: str = "rate_cap") -> int:
    """How many queries the window could send at the mix's ``rate`` (answers
    a second) with what may be in flight when it closes. At ``rate_cap``: the
    head of the list that set-up walks on the device. At ``list_rate``: the
    whole list, which set-up plans on the host, priming every program it
    reaches; a window that comes to its end is not correct."""
    w = mix["walk"]
    n = math.ceil(float(w[rate]) * (float(mix["lead_in_s"]) + seconds))
    return n + int(w["in_flight"])


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.25))


def delta(a: dict, b: dict) -> dict:
    return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in set(a) | set(b)}


def http_pass(port: int, path: str, queries: list[str], timeout: float
              ) -> int:
    ok = 0
    for q in queries:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}{urllib.parse.quote(q)}",
                timeout=timeout) as r:
            ok += r.status == 200 and bool(json.loads(r.read()))
    return ok


def main(argv, t0: float | None = None, hard_exit: bool = True,
         allow_cpu: bool = False, after_compare=None) -> None:
    """Runs the cell and ends the process (``hard_exit``) or raises
    SystemExit with the line kept on the ``Run`` (tests).
    ``after_compare(ctx)`` is the control tool's hook."""
    args = parse(argv)
    label = f"{args.workload}-s{args.seed}"
    run = watchdog.Run(label=label, t0=t0, hard_exit=hard_exit)
    main.last_run = run
    run.start_heartbeat()
    try:
        _run(run, args, allow_cpu, after_compare)
    except SystemExit:
        raise
    except BaseException as e:  # noqa: BLE001 -- the run ends here, loudly
        import traceback
        traceback.print_exc(file=run.err)
        run.say(error=repr(e), phase_was=run.phase_name)
        run.finish(None, 1)


def _run(run: watchdog.Run, args, allow_cpu: bool, after_compare) -> None:
    rehearse = args.rehearse_docs > 0
    try:
        bench = spec.benchmark()
        cell = spec.cell(args.workload, bench)
        import open_source_search_engine_tpu  # noqa: F401 -- is it here?
    except (ImportError, OSError, KeyError) as e:
        run.say(error=f"nothing to run here: {e!r}")
        run.finish(None, NO_PROGRAM)
    cfg, mix = cell["config_file"], cell["traffic_file"]
    docs = args.rehearse_docs or int(cfg["docs"])
    cover_path = spec.WORK / "cover" / f"{args.workload}.json"
    first_run = not cover_path.is_file()
    run.set_deadline(FIRST_RUN_LIMIT_S if first_run else RUN_LIMIT_S)
    run_dir = spec.WORK / "runs" / run.label
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)

    dep = spec.plugin("deployments", cfg["deployment"]["kind"]).Deployment(
        run, cfg, rehearse=rehearse)
    run.phase("corpus_start", docs=docs, first_run=first_run)
    dep.start_corpus(args.seed, docs)

    # the chip is reached on a thread of its own while the children index
    box: "queue.Queue" = queue.Queue()

    def reach():
        try:
            box.put(dep.reach_chip())
        except BaseException as e:  # noqa: BLE001 -- handed to the waiter
            box.put(e)
    threading.Thread(target=reach, name="reach-chip", daemon=True).start()
    device = run.get(box, 180.0, "reaching the chip")
    if isinstance(device, BaseException):
        raise device
    run.phase("device", **device, compile_cache=dep.cache_dir)
    if device["platform"] == "cpu" and not (rehearse or allow_cpu):
        run.say(error="jax found no accelerator (platform cpu)")
        run.finish(None, NO_CHIP)
    if not rehearse and not allow_cpu and device["count"] < cell["chips"]:
        run.say(error=f"{device['count']} chips, the cell needs "
                      f"{cell['chips']}")
        run.finish(None, NO_CHIP)
    if device["platform"] != "cpu":
        peaks = measure.peaks(device["kind"])
    else:
        peaks = None
    run.last_line = {"device": dict(device)}

    run.phase("corpus_wait", kept=dep.corpus_kept)
    dep.finish_corpus(limit=600.0 if first_run else 200.0)
    run.phase("device_base")
    info = dep.start()
    run.say(device_base=info, compiled=dep.compiled_since())

    # ------------------------------------------------------------ the walk
    rule = spec.plugin("queries", mix["queries"]["rule"])
    n_walk = walk_count(mix, args.seconds)
    n_list = max(walk_count(mix, args.seconds, "list_rate"), n_walk)
    n_wide = int(mix["walk"]["wide_plan"]) if first_run else 0
    if rehearse:
        n_wide = min(n_wide, 600)
    queries = rule.make(args.seed, max(n_list, n_wide),
                        mix["queries"]["params"])
    # programs are first met in one fixed order, whatever the seed: a
    # program's compile-cache key can depend on which program of its family
    # this process traced first (PERF.md, Findings PR 27), and the walk's own
    # order is the seed's
    run.phase("prime")
    order, left = cover.prime_order(
        dep.dry_keys(queries[:n_list]), n_walk, cover.known_keys(cover_path),
        dep.key_cost_class)
    primed = [dep.direct([queries[i]])[0] for i in order]
    run.phase("walk", queries=n_walk, listed=n_list, primed=primed,
              tail_unprimed=left,
              prime_compiled=dep.compiled_since())
    walk_keys = [dep.direct([q]) for q in queries[:n_walk]]
    run.phase("bursts", walk_compiled=dep.compiled_since())
    bursts = cover.plan_bursts(walk_keys, dep.key_class, dep.BURST)
    for b in bursts:
        b["rode"] = dep.direct([queries[i] for i in b["queries"]])
    run.say(bursts=[{k: b[k] for k in ("class", "key", "rode")}
                    for b in bursts])
    if first_run:
        run.phase("wide_plan", queries=n_wide)
        dry = dep.dry_keys(queries[:n_wide])
        wide = cover.plan_wide(dry, set(dep.keys), dep.key_cost_class,
                               mix["walk"]["wide_caps"])
        for w in wide:
            if not w["skipped"]:
                dep.direct([queries[w["query"]]])
        run.say(wide_plan=wide)
    had_file, new_keys = cover.check_file(cover_path, dep.keys)
    run.phase("cover", cover=sorted(dep.keys), cover_file=had_file,
              cost_s={k: round(v["first_s"], 1) for k, v in dep.keys.items()
                      if v["first_s"] > 5.0})
    for k in new_keys if had_file else []:
        run.say(cover_new_key=k["key"], cost_s=k["cost_s"],
                note="the checkout's cover file did not hold this key")

    # ------------------------------------------------- the pass over HTTP
    run.phase("http_pass")
    n_pass = int(mix["http_pass"])
    ok = http_pass(dep.port, mix["path"], queries[n_walk - n_pass:n_walk],
                   float(mix["timeout_s"]))
    if ok != n_pass:
        raise RuntimeError(f"HTTP pass: {ok} of {n_pass} answered")

    # ---------------------------------------------------------- the window
    run.phase("loadgen_start")
    plan = window_plan(mix, args.seed, args.seconds, queries[:n_list])
    (run_dir / "plan.json").write_text(json.dumps(plan))
    child = run.spawn(
        [sys.executable, str(spec.BENCH / "lib" / "loadgen.py"),
         "--port", str(dep.port), "--plan", str(run_dir / "plan.json"),
         "--out", str(run_dir / "rows.json")],
        stdin=-1, stdout=-1, stderr=sys.stderr, text=True)
    if run.read_line(child, 60.0, "load generator ready") != "ready":
        raise RuntimeError("load generator did not say ready")
    dep.seal()
    trace_dir = run_dir / "trace"
    lead, seconds = plan["lead_in"], args.seconds
    # the traced span closes with the window, and the trace stays small
    # enough to read in time
    trace_span = min(TRACE_SPAN_S, seconds)
    c_go = dep.counters()
    t_go = time.perf_counter()
    child.stdin.write("go\n")
    child.stdin.flush()
    setup_s = t_go + lead - run.t0
    run.phase("lead_in", setup_s=round(setup_s, 3))
    sleep_until(t_go + lead)
    c_open = dep.counters()
    run.phase("window", seconds=seconds)
    t_traced = None
    if args.trace:
        sleep_until(t_go + lead + seconds - trace_span)
        dep.start_trace(str(trace_dir))
        t_traced = time.perf_counter() - t_go
        run.phase("tracing", from_s=round(t_traced, 3))
    sleep_until(t_go + lead + seconds)
    c_close = dep.counters()
    run.phase("drain")
    said = run.read_line(child, plan["drain_s"] + plan["timeout_s"] + 30.0,
                         "load generator done")
    if said != "done":
        raise RuntimeError(f"load generator said {said!r}")
    run.wait_proc(child, 10.0, "load generator exit")
    if args.trace:
        run.phase("stop_trace")
        dep.stop_trace()
    c_end = dep.counters()
    memory_peak = dep.memory_peak()
    out = spec.load_json(run_dir / "rows.json")
    rows = out["rows"]
    win = {"rows": rows, "open": lead, "close": lead + seconds,
           "seconds": seconds, "setup_s": setup_s, "loop": plan["loop"],
           "timeout_s": plan["timeout_s"]}
    key = "due" if plan["loop"] == "open" else "sent"
    in_win = [r for r in rows if lead <= r[key] < lead + seconds]
    attempted = len(in_win)
    failed = sum(1 for r in in_win if r["status"] != 200)
    cw = delta(c_open, c_close)
    # what the yardstick itself has to hold for the window to count: nothing
    # compiled or loaded in it, and the list outlasted it
    own = {"window_compiles": cw.get("jit.compiles", 0.0),
           "list_ran_out": float(bool(out["ran_out"]))}
    sent_unwalked = sum(1 for r in rows if r["q"] >= n_walk)
    run.say(window={
        "attempted": attempted, "failed": failed,
        "sent_unwalked": sent_unwalked,
        "never_ended": out["never_ended"], **own,
        "late_ms_max": max([1000 * (r["sent"] - r["due"]) for r in rows]
                           + [0.0])})

    # ---- only now the reference: the window is closed, the peak is read
    run.phase("stop_server")
    stopper = threading.Thread(target=dep.stop, name="stop-server",
                               daemon=True)
    stopper.start()
    stopper.join(timeout=10.0)
    run.phase("compare")
    gen = spec.plugin("corpora", cfg["corpus"]["generator"])
    lens, ids = dep.words()
    ref = spec.plugin("reference", cfg["reference"]["name"]).Reference(
        lens, ids, cfg["corpus"]["params"],
        max_per_site=int(cfg["guarantees"]["max_per_site"]),
        page=int(cfg["deployment"]["page"]))
    finished = [r for r in rows if r["done"] >= lead and r["status"] != 0]
    sample = compare.draw_sample(finished, plan["queries"], args.seed,
                                 int(cfg["check"]["sample"]))
    span = delta(c_go, c_end)
    off_device = span.get("serve.device_fallback", 0.0) \
        + span.get("server.result_cache_hits", 0.0)
    memo: dict = {}

    def answer_of(q):
        if q not in memo:
            memo[q] = ref.answer(q)
        return memo[q]
    correct, numbers, notes = compare.compare(
        sample, plan["queries"], answer_of, gen.doc_of_url,
        int(cfg["guarantees"]["max_per_site"]), off_device,
        cfg["check"]["limits"])
    numbers.update({k: {"value": v, "limit": 0} for k, v in own.items()})
    correct = correct and attempted > 0 and not out["never_ended"] \
        and not any(own.values())
    if notes:
        run.say(compare_notes=notes)
    if after_compare is not None:
        after_compare({"run": run, "sample": sample, "ref": ref,
                       "queries": plan["queries"], "gen": gen, "cfg": cfg,
                       "lens": lens, "ids": ids, "numbers": numbers})

    # ------------------------------------------------------------- metrics
    device_out = {**device, "memory_peak_bytes": memory_peak}
    metrics, breakdown = {}, None
    if args.trace:
        run.phase("read_trace")
        span_s = lead + seconds - t_traced
        xp = trace_reduce.find_xplane(str(trace_dir))
        tr = trace_reduce.reduce(xp, span_s) if xp else None
        if tr and tr["busy_s"]:
            device_out["busy_s"] = tr["busy_s"]
            device_out["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["ops"][:10],
                         "idle_gaps": tr["gaps"][:10]}
        in_span = [r for r in rows if r["status"] == 200
                   and t_traced <= r["done"] < lead + seconds]
        ctx = {"counters": cw, "win": win, "trace": tr, "peaks": peaks,
               "is_wave": dep.wave_program,
               "answers_in_span": len(in_span),
               "needed_bytes": measure.needed_bytes(
                   [plan["queries"][r["q"]] for r in in_span],
                   gen.postings_per_word(lens, ids, cfg["corpus"]["params"]),
                   int(cfg["deployment"]["page"]))}
        if tr:
            run.say(trace={"planes": tr["planes"], "lines": tr["lines"],
                           "modules": sorted(tr["modules"].items(),
                                             key=lambda kv: -kv[1])[:12]})
        for m in spec.metrics_of(bench, args.workload, "per_layer"):
            v = spec.plugin("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in spec.metrics_of(bench, args.workload, "end_to_end"):
            v = spec.plugin("end_to_end", m["name"]).read(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    (run_dir / "rows.json").unlink(missing_ok=True)

    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device_out}
    if breakdown:
        line["breakdown"] = breakdown
    line["end_s"] = run.at()
    line["sent_unwalked"] = sent_unwalked
    line["compared"] = {k: [v["value"], v["limit"]]
                        for k, v in numbers.items()}
    run.phase("result", correct=bool(correct))
    for k, v in numbers.items():
        print(f"compared {k}: {v['value']:.6g} (limit {v['limit']:.6g})",
              file=run.err, flush=True)
    if rehearse or (allow_cpu and device["platform"] == "cpu"
                    and run.hard_exit):
        run.say(rehearsal_line=line)
        run.final_line = line
        run.finish(None, NO_CHIP)
    run.final_line = line
    run.finish(line, 0)
