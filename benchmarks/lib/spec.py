"""Where things are, and how a name in BENCHMARK.json finds its file.

The runner holds no list of cells, configurations, mixes or metrics: a cell is
an entry of ``workloads``; its configuration is ``configs/<config>.json``; its
traffic is ``traffic/<traffic>.json``; a generator, query rule, deployment,
reference or metric reader is ``<kind>/<name>.py``, loaded by its name.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]       # benchmarks/
REPO = BENCH.parent
WORK = BENCH / "_work"                            # everything a run leaves behind


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry with its configuration and traffic files loaded."""
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
            return {**w, "config_entry": cfg,
                    "config_file": load_json(REPO / cfg["file"]),
                    "traffic_file": load_json(
                        BENCH / "traffic" / f"{w['traffic']}.json")}
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def plugin(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, cell_name: str, group: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]
