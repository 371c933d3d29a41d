"""Device time of one ``_two_phase`` (F1) wave at ``B`` 4, by live lanes.

    python3 tools/f1_lane_bench.py [--root <checkout>] [--out <file.npz>]

Builds the resident columns at the widths of an 80,000-page shard
(``D_cap`` 131,072, 1,024 dense rows, 41,943,040 posting slots) from
random numbers on the device, then times one wave of each case: ``T`` 4 on
the ``(4, 4, 512)`` tier and ``T`` 8 on the ``(16, 16, 2048)`` tier, κ = k2
= 256, with 1, 2, 3 and 4 live lanes (the rest padding, as ``_f1_call``
pads them). Each case is compiled and run twice first; then the host clock
around ``block_until_ready`` over ``--reps`` waves gives the median and
the quartiles, in ms. Any run of fewer than 80,000 postings
holds distinct pages. ``--root`` imports the package from another
checkout (a program whose ``_two_phase`` takes no live count runs every
lane). One JSON line per case on stdout; ``--out`` keeps every case's
rows, to compare two checkouts' answers. ``--small`` runs the same cases
at a few thousand pages (a rehearsal on the CPU: its times mean nothing).
Not run by any benchmark cell.
"""

import argparse
import inspect
import json
import statistics
import sys
import time
from pathlib import Path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=str(Path(__file__).resolve().parents[1]))
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--out", default="")
    ap.add_argument("--small", action="store_true")
    a = ap.parse_args()
    sys.path.insert(0, a.root)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from open_source_search_engine_tpu.query import devindex as dv

    D, V, M, n_docs = (2048, 32, 65536, 1500) if a.small else (
        131072, 1024, 41943040, 80000)
    P, kappa = 16, 256
    looped = "n_live" in inspect.signature(dv._two_phase).parameters
    key = jax.random.split(jax.random.PRNGKey(a.seed), 8)
    ri = lambda k, n, hi, dt=jnp.int32: jax.random.randint(
        k, (n,), 0, hi, jnp.int32).astype(dt)
    cols = dict(
        d_payload=jax.random.bits(key[0], (M,), jnp.uint32),
        # a posting run holds each page once, as a term's list does (the
        # per-lane scatters ``set`` by page: a page twice in a run would
        # leave which write lands to the backend)
        d_doc=(jnp.arange(M, dtype=jnp.int32) % n_docs * 7919) % n_docs,
        d_imp=jax.random.uniform(key[2], (M,), jnp.float32, 0.01,
                                 1.0).astype(jnp.float16),
        d_rs=ri(key[3], M, M - P),
        d_cnt=ri(key[4], M, 5, jnp.uint8),
        d_dense_imp=jax.random.uniform(key[5], (V, D), jnp.float32, 0.0,
                                       1.0).astype(jnp.float16),
        d_dense_rs=ri(key[6], V * D, M - P),
        d_dense_cnt=ri(key[7], V * D, 5, jnp.uint8),
        d_siterank=jnp.zeros(D, jnp.uint8),
        d_doclang=jnp.zeros(D, jnp.uint8),
        d_dead=jnp.arange(D) >= n_docs)
    jax.block_until_ready(list(cols.values()))
    rng = np.random.default_rng(a.seed)
    out = {}
    for T, (Rd, Rs, Lsp) in ((4, (4, 4, 512)), (8, (16, 16, 2048))):
        for n in (1, 2, 3, 4):
            B = 4
            d_slot = np.full((B, Rd), -1, np.int32)
            d_slot[:n] = rng.integers(0, V, (n, Rd))
            grp = lambda r: np.tile(np.arange(r) % T, (B, 1)).astype(np.int32)
            s_start = np.zeros((B, Rs), np.int32)
            s_start[:n] = rng.integers(0, M - Lsp, (n, Rs))
            s_len = np.zeros((B, Rs), np.int32)
            s_len[:n] = rng.integers(Lsp // 2, Lsp + 1, (n, Rs))
            req = np.zeros((B, T), bool)
            req[:n] = True
            sel = np.zeros((B, T, V), np.float32)
            for b in range(n):
                for slot, g in zip(d_slot[b], grp(Rd)[b]):
                    sel[b, g, slot] += 1.0
            lanes = [d_slot, grp(Rd), np.zeros((B, Rd), np.int32),
                     np.ones((B, Rd), np.int32), np.zeros((B, Rd), np.uint32),
                     s_start, s_len, grp(Rs), np.zeros((B, Rs), np.int32),
                     np.ones((B, Rs), np.int32), np.zeros((B, Rs), np.uint32),
                     np.ones((B, Rs), bool),
                     np.full((B, T), 0.5, np.float32), req,
                     np.zeros((B, T), bool), req.copy(), req.copy(),
                     np.ones((B, dv.TABLE_SIZE), bool), np.zeros(B, np.int32)]
            head = [cols[k] for k in (
                "d_payload", "d_doc", "d_imp", "d_rs", "d_cnt", "d_dense_imp",
                "d_dense_rs", "d_dense_cnt", "d_siterank", "d_doclang",
                "d_dead")] + [jnp.int32(n_docs), jnp.zeros(D, bool),
                              jnp.zeros(D, jnp.float32), jnp.asarray(sel)]
            if looped:
                head.append(jnp.int32(n))
            args = head + [jnp.asarray(x) for x in lanes]
            statics = dict(n_positions=P, lsp=Lsp, kappa=kappa, k2=kappa,
                           use_table=False, use_filter=False,
                           use_sort=False)
            t0 = time.perf_counter()
            rows = dv._two_phase(*args, **statics).block_until_ready()
            first_s = time.perf_counter() - t0
            dv._two_phase(*args, **statics).block_until_ready()
            ms = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                dv._two_phase(*args, **statics).block_until_ready()
                ms.append(1e3 * (time.perf_counter() - t0))
            q = statistics.quantiles(ms, n=4)
            out[f"t{T}_n{n}"] = np.asarray(rows)[:n]
            print(json.dumps({
                "T": T, "tier": [Rd, Rs, Lsp], "kappa": kappa, "n_live": n,
                "looped": looped, "device": jax.devices()[0].device_kind,
                "first_s": round(first_s, 3), "median_ms": statistics.median(ms),
                "q1_ms": q[0], "q3_ms": q[2], "reps": a.reps,
                "matched": [int(r[0]) for r in np.asarray(rows)[:n]]}),
                flush=True)
    if a.out:
        np.savez(a.out, **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
