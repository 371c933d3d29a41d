"""Profiling harness for the resident query path (not part of the repo API)."""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

N_DOCS = int(os.environ.get("BENCH_DOCS", "2000"))
N_QUERIES = 96
BATCH = 32

import bench


def main():
    import jax
    print("devices:", jax.devices(), file=sys.stderr)
    from open_source_search_engine_tpu.index.collection import Collection
    from open_source_search_engine_tpu.query import engine

    coll = Collection("bench", tempfile.mkdtemp(prefix="osse_prof_"))
    t0 = time.perf_counter()
    vocab = bench._build_corpus(coll, N_DOCS)
    print(f"build: {time.perf_counter()-t0:.1f}s", file=sys.stderr)
    queries = bench._make_queries(vocab, N_QUERIES)
    batches = [queries[i:i + BATCH] for i in range(0, len(queries), BATCH)]

    di = engine.get_device_index(coll)
    print(f"base doc-runs={len(di.h_doc_col)} docs={di.n_docs}",
          file=sys.stderr)

    # warmup
    for b in batches:
        engine.search_device_batch(coll, b, topk=10, with_snippets=False)

    # plan-only timing
    from open_source_search_engine_tpu.query.compiler import compile_query
    plans = [compile_query(q, 0) for q in queries]
    t0 = time.perf_counter()
    for qp in plans:
        di.plan(qp)
    t_plan = time.perf_counter() - t0
    print(f"plan: {1000*t_plan/len(plans):.2f} ms/query", file=sys.stderr)

    # search_batch timing (includes device)
    t0 = time.perf_counter()
    for b in batches:
        di.search_batch(b, topk=20)
    t_sb = time.perf_counter() - t0
    print(f"search_batch total: {t_sb:.2f}s -> {N_QUERIES/t_sb:.1f} qps",
          file=sys.stderr)

    # full search_device_batch (includes result building)
    t0 = time.perf_counter()
    for b in batches:
        engine.search_device_batch(coll, b, topk=10, with_snippets=False)
    t_f = time.perf_counter() - t0
    print(f"full batch: {t_f:.2f}s -> {N_QUERIES/t_f:.1f} qps", file=sys.stderr)

    # single-query latency
    t0 = time.perf_counter()
    for q in queries[:20]:
        engine.search_device(coll, q, topk=10, with_snippets=False)
    lat = (time.perf_counter() - t0) / 20
    print(f"single-query: {1000*lat:.1f} ms", file=sys.stderr)

    # shape-bucket distribution
    from collections import Counter

    from open_source_search_engine_tpu.query.devindex import _f1_rows
    c = Counter()
    for qp in plans:
        p = di.plan(qp)
        if not p.matchable:
            c["unmatchable"] += 1
            continue
        c[_f1_rows(max(len(p.d_slot), 1), max(len(p.s_start), 1),
                   int(p.s_len.max()) if len(p.s_len) else 0)] += 1
    print("shape buckets (Rd,Rs,Lsp):", dict(c), file=sys.stderr)
    print(f"escalations: {di.escalations}", file=sys.stderr)


if __name__ == "__main__":
    main()
