"""jitwatch — runtime compile/retrace/transfer attribution.

The runtime half of the jit analysis plane: a forced retrace must be
attributed to its call site, explicit transfers must be counted and
keyed by site, OSSE_JITWATCH=0 must be a true no-op (no patched
entry points, no log handlers, no config flip, no counters), and
enable/disable must restore every hook exactly.
"""

import itertools
import json
import logging
import subprocess
import sys
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from open_source_search_engine_tpu.utils import jitwatch
from open_source_search_engine_tpu.utils.stats import g_stats

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def watch():
    """Enabled watcher with a fresh table; restores the pre-test
    enablement (tier-1 runs both with and without OSSE_JITWATCH=1)."""
    was = jitwatch.enabled()
    jitwatch.enable()
    jitwatch.reset()
    yield jitwatch.g_jitwatch
    jitwatch.reset()
    if not was:
        jitwatch.disable()


def test_retrace_attributed_to_call_site(watch):
    @jax.jit
    def _probe(x):
        return x + 1

    small = jnp.ones((4,), jnp.float32)
    big = jnp.ones((16,), jnp.float32)  # built pre-reset: jnp.ones
    # itself cold-traces an internal broadcast per shape
    _probe(small)  # cold: first trace
    jitwatch.reset()
    _probe(big)  # new shape: retrace
    snap = jitwatch.snapshot()
    assert snap["totals"]["retraces"] == 1
    assert snap["totals"]["first_traces"] == 0
    assert snap["totals"]["compiles"] >= 1
    ev = [e for e in snap["events"] if e["kind"] == "retrace"]
    assert ev, snap["events"]
    # the site is THIS file and the miss explanation names the cause
    assert "test_jitwatch.py" in ev[0]["site"]
    assert ev[0]["fn"] == "_probe"
    assert "different input types" in ev[0]["last"]
    assert snap["totals"]["compile_s"] > 0.0
    ctr = g_stats.snapshot()["counters"]
    assert any(k.startswith("jit.retrace.") for k in ctr)


def test_steady_state_is_quiet(watch):
    @jax.jit
    def _probe2(x):
        return x * 2

    _probe2(jnp.ones((8,), jnp.float32))
    jitwatch.reset()
    for _ in range(4):
        _probe2(jnp.ones((8,), jnp.float32))  # warm: same shape
    t = jitwatch.snapshot()["totals"]
    assert t["compiles"] == 0 and t["retraces"] == 0


def test_transfer_events_counted_and_sited(watch):
    x = jnp.ones((8,), jnp.float32)
    x.block_until_ready()
    jitwatch.reset()
    jax.device_get(x)
    snap = jitwatch.snapshot()
    assert snap["totals"]["transfers"] == 1
    ev = [e for e in snap["events"] if e["kind"] == "transfer"]
    assert ev[0]["fn"] == "device_get"
    assert "test_jitwatch.py" in ev[0]["site"]
    assert ev[0]["bytes"] == 32
    # tests/ is not a blessed device-boundary module
    assert not ev[0]["boundary"]
    assert snap["totals"]["transfers_offboundary"] == 1
    assert not jitwatch.is_boundary_site(ev[0]["site"])
    assert jitwatch.is_boundary_site("query/devindex.py:1582")


def test_enable_disable_restores_hooks():
    was = jitwatch.enabled()
    jitwatch.enable()
    assert not jax.device_get.__module__.startswith("jax")
    jitwatch.disable()
    # entry points, handlers, and logger state all restored
    assert jax.device_get.__module__.startswith("jax")
    assert jax.device_put.__module__.startswith("jax")
    for name in jitwatch._JAX_LOGGERS:
        lg = logging.getLogger(name)
        assert jitwatch.g_jitwatch._handler not in lg.handlers
    if was:
        jitwatch.enable()


def test_off_is_true_noop():
    """With OSSE_JITWATCH unset, importing the device layer must not
    patch jax, hook loggers, flip config, or mint jit.* counters."""
    code = (
        "import os\n"
        "os.environ.pop('OSSE_JITWATCH', None)\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import logging\n"
        "import jax\n"
        "from open_source_search_engine_tpu.utils import jitwatch\n"
        "from open_source_search_engine_tpu.query import devindex\n"
        "assert not jitwatch.enabled()\n"
        "assert jax.device_get.__module__.startswith('jax')\n"
        "assert jax.device_put.__module__.startswith('jax')\n"
        "assert not jax.config.jax_explain_cache_misses\n"
        "for n in jitwatch._JAX_LOGGERS:\n"
        "    assert not logging.getLogger(n).handlers\n"
        "from open_source_search_engine_tpu.utils.stats import g_stats\n"
        "ctr = g_stats.snapshot()['counters']\n"
        "assert not any(k.startswith('jit.') for k in ctr), ctr\n"
        "print('NOOP-OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr
    assert "NOOP-OK" in proc.stdout


def test_env_enables_via_device_layer_import():
    """OSSE_JITWATCH=1 + importing devindex turns the watcher on —
    no entry point has to opt in."""
    code = (
        "import os\n"
        "os.environ['OSSE_JITWATCH'] = '1'\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from open_source_search_engine_tpu.query import devindex\n"
        "from open_source_search_engine_tpu.utils import jitwatch\n"
        "assert jitwatch.enabled()\n"
        "print('ON-OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr
    assert "ON-OK" in proc.stdout


def test_admin_jit_page(tmp_path, watch):
    """/admin/jit serves the attribution table in HTML and JSON."""
    from open_source_search_engine_tpu.serve.server import \
        SearchHTTPServer
    jax.device_get(jnp.ones((4,), jnp.float32))
    s = SearchHTTPServer(tmp_path, port=0)
    s.start()
    try:
        base = f"http://127.0.0.1:{s._httpd.server_port}"
        html = urllib.request.urlopen(f"{base}/admin/jit").read()
        assert b"jit plane" in html and b"watcher enabled" in html
        js = json.loads(urllib.request.urlopen(
            f"{base}/admin/jit?format=json").read())
        assert js["enabled"]
        assert js["totals"]["transfers"] >= 1
        assert any(e["kind"] == "transfer" for e in js["events"])
        assert any(k.startswith("jit.transfer.")
                   for k in js["counters"])
    finally:
        s.stop()


def test_cold_trace_is_not_a_retrace(watch):
    """A never-seen function's first trace is a first_trace naming the
    function — the smoke's "retraces after warm-up" must not count a
    route's first compile."""
    @jax.jit
    def _cold(x):
        return x - 1

    x = jnp.ones((4,), jnp.float32)
    jitwatch.reset()
    _cold(x)
    snap = jitwatch.snapshot()
    assert snap["totals"]["first_traces"] == 1
    assert snap["totals"]["retraces"] == 0
    ev = [e for e in snap["events"] if e["kind"] == "first_trace"]
    assert ev[0]["fn"] == "_cold"
    assert "test_jitwatch.py" in ev[0]["site"]


# --- steady-state discipline over the program's own waves ------------------

def _tiny_docs(n, tag):
    """Pages of one fixed shape (same word count, same title length)."""
    return [(f"http://{tag}{i % 7}.jit.test/d{i}",
             f"<html><head><title>Jit page {tag}</title></head><body>"
             f"<p>steady words filler token{i % 11} extra{i % 5} "
             f"rare{i % 13} bucket probe.</p></body></html>")
            for i in range(n)]


#: 1-3 words, with and without a match: several plan shapes a wave
_WAVE_QUERIES = ["steady", "words token3", "filler extra2 rare5",
                 "token7", "rare9 probe", "steady bucket filler",
                 "extra4", "nothinghere", "token1 token2"]
#: plans a wave, crossing the batch buckets (4 | 8) in both directions
_WAVE_SIZES = (1, 3, 5, 2, 8, 4)


def _resident_waves(tmp_path):
    from open_source_search_engine_tpu.build import docproc
    from open_source_search_engine_tpu.index.collection import Collection
    from open_source_search_engine_tpu.query import engine

    coll = Collection("jitres", tmp_path)
    docproc.index_batch(coll, _tiny_docs(60, "r"))
    plans = [engine._compile_cached(q, 0) for q in _WAVE_QUERIES]
    loop = engine.get_resident_loop(coll)
    # one ticket in flight: the loop merges whatever is queued when it
    # takes a batch, so two in flight make a wave's size a matter of
    # timing, and a size the warm-up never met compiles by right
    steps = [lambda n=n: loop.submit(plans[:n], topk=10).wait(timeout=120)
             for n in _WAVE_SIZES]
    return steps, loop.stop


def _mesh_waves(tmp_path):
    from open_source_search_engine_tpu.parallel.sharded import (
        MeshResident, ShardedCollection)
    from open_source_search_engine_tpu.query import engine

    sc = ShardedCollection("jitmesh", tmp_path, n_shards=4)
    for url, html in _tiny_docs(48, "m"):
        sc.index_document(url, html)
    mr = MeshResident(sc)
    msi = mr._serve_index()
    plans = [engine._compile_cached(q, 0) for q in _WAVE_QUERIES]
    steps = [lambda n=n: msi.collect_batch(
                 msi.issue_batch(plans[:n], topk=10))
             for n in _WAVE_SIZES]
    return steps, mr.stop


def _delta_folds(tmp_path):
    from open_source_search_engine_tpu.build import docproc
    from open_source_search_engine_tpu.index.collection import Collection
    from open_source_search_engine_tpu.query.devindex import DeviceIndex

    coll = Collection("jitfold", tmp_path)
    docproc.index_batch(coll, _tiny_docs(40, "b"))
    coll.posdb.dump()
    coll.titledb.dump()
    idx = DeviceIndex(coll)
    wave = itertools.count(1)

    def fold():
        # the memtable is folded whole at every refresh: 16 more pages
        # of the one shape each time, all inside one padded bucket
        docproc.index_batch(coll, _tiny_docs(16, f"f{next(wave)}x"))
        assert idx.refresh()

    return [fold] * 3, lambda: None


@pytest.mark.parametrize("case", ["resident", "mesh", "delta_fold"])
def test_steady_state_waves_compile_and_sync_nothing(case, tmp_path, watch):
    """After the warm passes, the same waves again: no compile, no
    retrace, and no transfer outside the blessed boundary sites —
    resident-loop waves and mesh waves of batch sizes on both sides of
    a bucket boundary, and delta folds that stay in one bucket."""
    steps, stop = {"resident": _resident_waves, "mesh": _mesh_waves,
                   "delta_fold": _delta_folds}[case](tmp_path)
    try:
        # two warm passes: a pruning miss in the first raises the cached
        # plan's ``kappa_min``, and the query's second meeting may then
        # ride another program (a `_direct_cube` where F1 escalated)
        for _ in range(2):
            for step in steps:
                step()
        jitwatch.reset()
        for _ in range(2):
            for step in steps:
                step()
        snap = jitwatch.snapshot()
    finally:
        stop()
    t = snap["totals"]
    noisy = [(e["kind"], e["site"], e.get("fn")) for e in snap["events"]
             if e["kind"] in ("retrace", "first_trace")
             or (e["kind"] == "transfer" and not e["boundary"])]
    assert (t["compiles"], t["retraces"], t["transfers_offboundary"]) \
        == (0, 0, 0), noisy
    assert t["transfers"] > 0, "the waves never crossed the boundary?"
