"""The control: the reference put in the program's place, one step down.

The configuration states exact answers in the program's float32. The step that
would tempt a later PR is the next precision down, bfloat16: the control is
the plain reference with every position weight rounded to bfloat16 (8 bits of
mantissa), answering the very queries a run sampled; the comparison has to
call it not correct. (float16 is no control here: scores pass 65,504.)

    python3 benchmarks/tools/control.py <run.py's arguments>
        a whole run on the chip; after its comparison the controls answer the
        run's own sample, and a ``control`` line on stderr gives every number
        they read beside the run's (PERF.md: the limits' upper readings)
    python3 benchmarks/tools/control.py --self-test <pages>
        on the CPU, no chip: the reference against the program's host flat
        path (engine.search) on 60+ queries, and each control through the
        comparison (benchmarks/tests/test_run.py)
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

import numpy as np  # noqa: E402

from lib import compare, spec  # noqa: E402


def to_bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), as float64."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.view(np.float32).astype(np.float64)


def controls(ref_mod, lens, ids, cfg) -> dict:
    kw = dict(max_per_site=int(cfg["guarantees"]["max_per_site"]),
              page=int(cfg["deployment"]["page"]))
    p = cfg["corpus"]["params"]
    return {"bf16_weights": ref_mod.Reference(lens, ids, p,
                                              weight_round=to_bf16, **kw)}


def in_the_programs_place(ctl, queries: list[str], qis: list[int], gen,
                          corpus: dict) -> list[dict]:
    """The control's answers, worded as the program words its own."""
    rows = []
    for qi in qis:
        a = ctl.answer(queries[qi])
        body = {"totalMatches": a["total"], "results": [
            {"url": gen.url_of(d, corpus), "score": s}
            for d, s in zip(a["page_docs"], a["ladder"])]}
        rows.append({"q": qi, "status": 200, "body": json.dumps(body)})
    return rows


def read_controls(ctx: dict) -> dict:
    cfg, gen = ctx["cfg"], ctx["gen"]
    ref_mod = spec.plugin("reference", cfg["reference"]["name"])
    out = {}
    for name, ctl in controls(ref_mod, ctx["lens"], ctx["ids"], cfg).items():
        rows = in_the_programs_place(ctl, ctx["queries"],
                                     [r["q"] for r in ctx["sample"]], gen,
                                     cfg["corpus"]["params"])
        ok, numbers, _ = compare.compare(
            rows, ctx["queries"], ctx["ref"].answer, gen.doc_of_url,
            int(cfg["guarantees"]["max_per_site"]), 0.0,
            cfg["check"]["limits"])
        out[name] = {"correct": ok,
                     **{k: v["value"] for k, v in numbers.items()}}
    return out


def on_chip(argv: list[str]) -> None:
    from lib import runner

    def hook(ctx):
        ctx["run"].say(control=read_controls(ctx),
                       program={k: v["value"]
                                for k, v in ctx["numbers"].items()})
    runner.main(argv, t0=T0, after_compare=hook)


def self_test(pages: int) -> int:
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from open_source_search_engine_tpu.build import docproc
    from open_source_search_engine_tpu.index.collection import Collection
    from open_source_search_engine_tpu.query import engine
    cfg = spec.load_json(BENCH / "configs" / "gbshard-80k.json")
    mix = spec.load_json(BENCH / "traffic" / "mix-c32.json")
    p = cfg["corpus"]["params"]
    gen = spec.plugin("corpora", cfg["corpus"]["generator"])
    ref_mod = spec.plugin("reference", cfg["reference"]["name"])
    seed = 4242
    with tempfile.TemporaryDirectory() as base:
        coll = Collection("main", base)
        docproc.index_batch(coll, list(gen.pages(seed, 0, pages, p)))
        coll.conf.pqr_enabled = False
        coll.dump_all()
        lens, ids = gen.word_ids(seed, 0, pages, p)
        ref = ref_mod.Reference(lens, ids, p)
        queries = spec.plugin("queries", mix["queries"]["rule"]).make(
            seed, 72, mix["queries"]["params"])
        rows = []
        for qi, q in enumerate(queries):
            r = engine.search(coll, q, topk=10, with_snippets=False)
            rows.append({"q": qi, "status": 200, "body": json.dumps({
                "totalMatches": r.total_matches,
                "results": [{"url": x.url, "score": x.score}
                            for x in r.results]})})
        ok, numbers, notes = compare.compare(
            rows, queries, ref.answer, gen.doc_of_url, 2, 0.0,
            cfg["check"]["limits"])
        bad = 0 if ok else len(notes)
        ctx = {"cfg": cfg, "gen": gen, "lens": lens, "ids": ids,
               "queries": queries, "sample": rows[:24], "ref": ref}
        out = {"host_flat_path": {
                   "queries": len(queries), "mismatches": bad,
                   **{k: v["value"] for k, v in numbers.items()}},
               "controls": read_controls(ctx)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--self-test":
        sys.exit(self_test(int(sys.argv[2]) if len(sys.argv) > 2 else 600))
    on_chip(sys.argv[1:])
