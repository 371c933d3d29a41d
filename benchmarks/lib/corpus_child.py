"""A corpus child: indexes one slice of the pages through the program's real
pipeline (``docproc.index_batch``) into a collection of its own, and keeps the
slice's word ids for the reference. Started by exec, held to the CPU: it never
reaches for the chip.

    python3 corpus_child.py --generator G --params JSON --seed S --lo A --hi B --out DIR
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"     # this process must not take the chip

HERE = Path(__file__).resolve()
sys.path.insert(0, str(HERE.parents[2]))
sys.path.insert(0, str(HERE.parents[1]))

BATCH = 512


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--generator", required=True)
    ap.add_argument("--params", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lo", type=int, required=True)
    ap.add_argument("--hi", type=int, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    import numpy as np

    from lib import spec
    from open_source_search_engine_tpu.build import docproc
    from open_source_search_engine_tpu.index.collection import Collection
    from open_source_search_engine_tpu.utils.membudget import g_membudget

    g_membudget.set_limit(8 << 30)
    gen = spec.plugin("corpora", a.generator)
    params = json.loads(a.params)
    coll = Collection("main", a.out)
    chunk: list = []
    for page in gen.pages(a.seed, a.lo, a.hi, params):
        chunk.append(page)
        if len(chunk) >= BATCH:
            docproc.index_batch(coll, chunk, propagate=False)
            chunk = []
    if chunk:
        docproc.index_batch(coll, chunk, propagate=False)
    coll.dump_all()
    coll.save()
    lens, ids = gen.word_ids(a.seed, a.lo, a.hi, params)
    np.savez(Path(a.out) / "words.npz", lens=lens, ids=ids)
    if coll.num_docs != a.hi - a.lo:
        print(f"indexed {coll.num_docs} of {a.hi - a.lo}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
