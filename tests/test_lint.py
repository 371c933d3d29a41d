"""osselint gate — the tree must be invariant-clean, fast, and the
rules themselves must keep working.

This is the tier-1 single lint gate: it replaced the string-match
lints that used to live in test_oddments.py (urlopen-in-parallel,
off-plane TtlCache) and test_trace.py (bare g_stats.timed on the query
path) — those invariants are now AST rules in ``tools/osselint.py``,
exercised here against fixtures with known-violating and known-clean
code, plus seeded regressions for bugs this repo actually shipped
(the PR 4 ``id(conf)`` cache key).
"""

import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from tools import osselint

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "tests" / "lint_fixtures"


def _lint_file(path: Path):
    return osselint.check_source(path.read_text(encoding="utf-8"),
                                 path.relative_to(ROOT).as_posix())


class TestTreeIsClean:
    def test_zero_unwaived_findings_under_budget(self):
        """The whole package + tools + tests lint clean in < 5s of the
        linter's own CPU time (not wall time: the suite's other xdist
        workers compile TPU kernels next to this test) — osselint is
        cheap enough to gate every PR."""
        t0 = time.process_time()
        files = osselint.iter_py_files(osselint.default_paths(ROOT),
                                       ROOT)
        findings = osselint.lint_files(files, ROOT)
        elapsed = time.process_time() - t0
        assert not findings, "\n".join(
            f"{f.path}:{f.line}: {f.rule}: {f.msg}" for f in findings)
        assert len(files) > 100, "scan missed most of the tree?"
        assert elapsed < 5.0, f"osselint took {elapsed:.1f}s (budget 5s)"

    def test_fixtures_are_excluded_from_tree_scan(self):
        files = osselint.iter_py_files(osselint.default_paths(ROOT),
                                       ROOT)
        assert not any("lint_fixtures" in f.parts for f in files)


def _violation_fixtures():
    return sorted(FIXTURES.glob("violations_*.py"))


class TestFixtures:
    @pytest.mark.parametrize(
        "fixture", _violation_fixtures(), ids=lambda p: p.stem)
    def test_every_rule_fires_where_expected(self, fixture):
        """Each violations fixture carries ``# EXPECT rule`` markers;
        the finding set must equal the marker set exactly — no missed
        violations, no spurious ones."""
        expected = set()
        for i, line in enumerate(fixture.read_text().splitlines(),
                                 start=1):
            for rule in re.findall(r"# EXPECT ([a-z\-]+)", line):
                expected.add((i, rule))
        got = {(f.line, f.rule) for f in _lint_file(fixture)}
        assert got == expected, (
            f"missed: {sorted(expected - got)}\n"
            f"spurious: {sorted(got - expected)}")

    def test_all_rules_covered_by_fixture(self):
        """Every registered rule has at least one positive case
        somewhere in the violations fixtures."""
        covered = set()
        for fixture in _violation_fixtures():
            covered |= set(re.findall(r"# EXPECT ([a-z\-]+)",
                                      fixture.read_text()))
        assert covered == osselint.RULE_NAMES

    @pytest.mark.parametrize(
        "fixture", sorted(FIXTURES.glob("clean_*.py")),
        ids=lambda p: p.stem)
    def test_clean_fixture_has_no_findings(self, fixture):
        findings = _lint_file(fixture)
        assert not findings, [(f.line, f.rule) for f in findings]

    def test_waiver_suppresses_and_scopes_to_named_rule(self):
        src = ("# osselint: path=open_source_search_engine_tpu/"
               "parallel/w.py\n"
               "import time\n"
               "import threading\n"
               "_lock = threading.Lock()\n"
               "def f():\n"
               "    with _lock:\n"
               "        time.sleep(1)  # osselint: ignore["
               "blocking-under-lock] — fixture\n")
        assert osselint.check_source(src, "x.py") == []
        # a waiver for a DIFFERENT rule must not suppress
        wrong = src.replace("ignore[blocking-under-lock]",
                            "ignore[id-key]")
        found = osselint.check_source(wrong, "x.py")
        assert [f.rule for f in found] == ["blocking-under-lock"]


class TestSeededRegressions:
    """Re-lint the literal bug shapes this repo shipped before."""

    def test_pr4_id_conf_cache_key_is_caught(self):
        # the PR 4 SERP-cache bug: conf keyed by id() — address reuse
        # after GC aliases a dead conf to a live one
        src = ("def serp_key(conf, q):\n"
               "    return (q, id(conf))\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/sharded.py")
        assert [f.rule for f in found] == ["id-key"]

    def test_offplane_ttlcache_is_caught(self):
        src = ("from ..utils.ttlcache import TtlCache\n"
               "c = TtlCache(max_items=10)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/serve/server.py")
        assert [f.rule for f in found] == ["ttlcache-offplane"]
        # ...but the cache plane itself may construct them
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/cache/plane.py") == []

    def test_bare_urlopen_in_parallel_is_caught(self):
        src = ("import urllib.request\n"
               "def get(u):\n"
               "    return urllib.request.urlopen(u)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/cluster.py")
        assert {f.rule for f in found} == {"urllib-in-parallel"}
        # transport.py is the sanctioned courier
        assert osselint.check_source(
            src,
            "open_source_search_engine_tpu/parallel/transport.py") == []

    def test_mesh_collective_outside_mesh_plane_is_caught(self):
        # the mesh-serving PR's layering rule: the Msg3a merge program
        # in parallel/sharded.py is the ONE home for ICI collectives —
        # a stray all_gather in the scorer couples the flat single-chip
        # kernel to the serving mesh shape
        src = ("import jax\n"
               "def merge(scores):\n"
               "    return jax.lax.all_gather(scores, 'shards')\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/scorer.py")
        assert [f.rule for f in found] == ["mesh-collective"]
        # ...but the mesh plane itself is the sanctioned home
        assert osselint.check_source(
            src,
            "open_source_search_engine_tpu/parallel/sharded.py") == []

    def test_bare_stats_timed_on_query_path_is_caught(self):
        src = ("def search(q):\n"
               "    with g_stats.timed('query.total'):\n"
               "        pass\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/engine.py")
        assert [f.rule for f in found] == ["bare-stats-timed"]
        # outside the query path the plane is free to use it
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/utils/stats.py") == []

    def test_dynamic_stat_name_is_caught_and_table_fixes_it(self):
        # the literal pre-telemetry devindex shape: one time series
        # per observed wave count (devindex.wave_f1+f2_n5, _n7, ...)
        src = ("def collect(kinds, waves, t0, t1):\n"
               "    trace.record(\n"
               "        f'devindex.wave_{kinds}_n{len(waves)}',"
               " t0, t1)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/devindex.py")
        assert [f.rule for f in found] == ["stats-cardinality"]
        # the fix: bucket the count, look the name up from a literal
        # module-level table (f-strings OUTSIDE a stats call are fine)
        fixed = ("_WAVE_STAT = {n: f'devindex.wave_n{n}'\n"
                 "              for n in (1, 2, 4, 8)}\n"
                 "def collect(kinds, waves, t0, t1):\n"
                 "    stat = _WAVE_STAT.get(min(len(waves), 8))\n"
                 "    if stat is not None:\n"
                 "        trace.record(stat, t0, t1)\n")
        assert osselint.check_source(
            fixed,
            "open_source_search_engine_tpu/query/devindex.py") == []
        # the rule is scoped to the query plane
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/serve/server.py") == []

    def test_adhoc_timing_on_query_path_is_caught(self):
        # the literal devindex/engine shape the metrics-plane PR
        # removed: a perf_counter delta feeding g_stats directly, so
        # the interval never reaches the trace waterfall
        src = ("import time\n"
               "def collect(waves):\n"
               "    t0 = time.perf_counter()\n"
               "    out = fetch(waves)\n"
               "    g_stats.record_ms('devindex.wave',\n"
               "                      1000 * (time.perf_counter() - t0))\n"
               "    return out\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/devindex.py")
        assert [f.rule for f in found] == ["adhoc-timing"]
        # the stats plane itself measures however it likes
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/utils/stats.py") == []
        # monotonic budget arithmetic is not latency measurement
        mono = ("import time\n"
                "def hedge_wait(t0):\n"
                "    return time.monotonic() - t0\n")
        assert osselint.check_source(
            mono, "open_source_search_engine_tpu/parallel/cluster.py") \
            == []

    def test_proc_spawn_outside_fleet_plane_is_caught(self):
        # the literal pre-fleet shape: tests/test_cluster.py Popen'd
        # node processes by hand and killed them with raw os.kill —
        # orphans survived any test body that raised
        src = ("import os\n"
               "import subprocess\n"
               "def boot(argv, pid):\n"
               "    p = subprocess.Popen(argv)\n"
               "    os.kill(pid, 9)\n"
               "    return p\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/cluster.py")
        assert [f.rule for f in found] == ["proc-spawn", "proc-spawn"]
        found = osselint.check_source(src, "tests/test_cluster.py")
        assert [f.rule for f in found] == ["proc-spawn", "proc-spawn"]
        # the fleet and chaos planes ARE the sanctioned owners...
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/fleet.py") \
            == []
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/utils/chaos.py") == []
        # ...and tools/ scripts run outside the serving tree
        assert osselint.check_source(src, "tools/opsctl.py") == []
        # method calls on an owned handle stay legal everywhere
        legal = ("def stop(proc):\n"
                 "    proc.kill()\n"
                 "    proc.send_signal(15)\n")
        assert osselint.check_source(
            legal,
            "open_source_search_engine_tpu/parallel/cluster.py") == []

    def test_residency_bypass_outside_tenancy_plane_is_caught(self):
        # the literal pre-tenancy shape: sharded.py built a DeviceIndex
        # per shard and spun its own ResidentLoop — HBM buffers the
        # ResidencyManager never saw, so the tenant LRU couldn't evict
        # them, the 'device' label never billed them, and delColl
        # couldn't unserve them
        src = ("from ..query.devindex import DeviceIndex\n"
               "from ..query.resident import ResidentLoop\n"
               "def boot(coll):\n"
               "    di = DeviceIndex(coll)\n"
               "    return ResidentLoop(lambda: di, lambda: 0)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/sharded.py")
        assert [f.rule for f in found] == ["residency-bypass",
                                          "residency-bypass"]
        # the residency plane and the engine factories ARE the owners
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/serve/tenancy.py") == []
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/query/engine.py") == []
        # tests construct loops directly against fakes — out of scope
        assert osselint.check_source(src, "tests/test_resident.py") == []

    def test_host_sort_in_ingest_plane_is_caught(self):
        # the pre-PR-16 shape: _build_base's merge/docidx ran as host
        # numpy orderings (np.unique + argsort over the whole corpus) —
        # exactly the O(corpus) CPU stage the device ingest plane
        # removed. Re-introducing one in devbuild.py must fire.
        src = ("import numpy as np\n"
               "def docidx_of(docids):\n"
               "    uniq = np.unique(docids)\n"
               "    return np.searchsorted(uniq, docids)\n"
               "def order(keys):\n"
               "    return sorted(keys)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/build/devbuild.py")
        assert [f.rule for f in found] == ["host-sort", "host-sort"]
        # the host oracle pipeline keeps its numpy orderings
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/query/devindex.py") == []
        # and the device orderings the fence steers toward stay clean
        dev = ("import jax.numpy as jnp\n"
               "def order(keys):\n"
               "    return jnp.argsort(keys, stable=True)\n")
        assert osselint.check_source(
            dev, "open_source_search_engine_tpu/build/devbuild.py") == []


class TestJitSeededRegressions:
    """The literal jit hazard shapes the PR 7 rules caught (or
    deliberately exempt) in the live tree."""

    def test_unbucketed_local_k_is_caught_and_bucket_fixes_it(self):
        # the sharded.py bug: local_k derived from topk+offset and a
        # len() max — one shard_map compile per distinct page size
        src = ("import jax\n"
               "def _impl(x, local_k):\n"
               "    return x[:local_k]\n"
               "_shard = jax.jit(_impl, static_argnames=('local_k',))\n"
               "def dispatch(x, plans, topk, offset):\n"
               "    D = max(len(p) for p in plans)\n"
               "    k = min(topk + offset, D)\n"
               "    return _shard(x, local_k=k)\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/parallel/mesh.py")
        assert [f.rule for f in found] == ["jit-unstable-static"]
        fixed = src.replace("k = min(topk + offset, D)",
                            "k = min(_bucket(topk + offset), D)")
        assert osselint.check_source(
            fixed,
            "open_source_search_engine_tpu/parallel/mesh.py") == []

    def test_cached_jit_factory_is_exempt(self):
        # devcheck._checked: an lru_cache'd factory mints one wrapper
        # per key — the safe jit-in-body idiom
        src = ("import functools\n"
               "import jax\n"
               "@functools.lru_cache(maxsize=None)\n"
               "def _checked(name):\n"
               "    return jax.jit(lambda x: x)\n")
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/query/devcheck.py") \
            == []
        bare = src.replace(
            "@functools.lru_cache(maxsize=None)\n", "")
        found = osselint.check_source(
            bare, "open_source_search_engine_tpu/query/devcheck.py")
        assert [f.rule for f in found] == ["jit-in-body"]

    def test_donated_rebind_idiom_is_exempt(self):
        # devindex._build_delta: self.d_X = _write_tail(self.d_X, ...)
        # rebinds the donated buffer — safe; reading it without the
        # rebind is the hazard
        src = ("import jax\n"
               "_wt = jax.jit(lambda b, v: b, donate_argnums=(0,))\n"
               "class D:\n"
               "    def build(self, v):\n"
               "        self.d_pos = _wt(self.d_pos, v)\n"
               "        return self.d_pos\n")
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/query/devindex.py") \
            == []
        bad = src.replace("self.d_pos = _wt(self.d_pos, v)",
                          "out = _wt(self.d_pos, v)")
        found = osselint.check_source(
            bad, "open_source_search_engine_tpu/query/devindex.py")
        assert [f.rule for f in found] == ["jit-donated-reuse"]


class TestCli:
    def test_violating_files_exit_nonzero_with_json(self):
        fixtures = _violation_fixtures()
        proc = subprocess.run(
            [sys.executable, "-m", "tools.osselint", "--format=json"]
            + [str(f) for f in fixtures],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        import json
        payload = json.loads(proc.stdout)
        assert payload["files"] == len(fixtures)
        assert {f["rule"] for f in payload["findings"]} \
            == osselint.RULE_NAMES

    def test_clean_file_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.osselint",
             str(FIXTURES / "clean_parallel.py")],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout

    def test_changed_mode_exits_nonzero_on_findings(self, tmp_path):
        """--changed over a scratch repo holding one violating file."""
        repo = tmp_path / "repo"
        pkg = repo / "open_source_search_engine_tpu" / "parallel"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text(
            "import urllib.request\n"
            "x = urllib.request.urlopen('http://example.com')\n")
        for args in (["git", "init", "-q"],
                     ["git", "add", "-A"],
                     ["git", "-c", "user.email=t@t", "-c",
                      "user.name=t", "commit", "-qm", "seed"]):
            subprocess.run(args, cwd=repo, check=True,
                           capture_output=True)
        # modify post-commit so it shows up as changed vs. HEAD
        (pkg / "bad.py").write_text(
            "import urllib.request\n"
            "y = urllib.request.urlopen('http://example.org')\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.osselint", "--changed",
             "--root", str(repo)],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1
        assert "urllib-in-parallel" in proc.stdout
        # and a clean tree (nothing changed) exits 0
        subprocess.run(["git", "checkout", "-q", "--", "."], cwd=repo,
                       check=True, capture_output=True)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.osselint", "--changed",
             "--root", str(repo)],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stdout

    def test_changed_mode_handles_rename_and_delete(self, tmp_path):
        """A staged rename must be linted under its NEW path and a
        staged delete must contribute nothing — neither may crash the
        diff parse (R/C rows carry two paths, D rows a missing file)."""
        repo = tmp_path / "repo"
        pkg = repo / "open_source_search_engine_tpu" / "parallel"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text(
            "import urllib.request\n"
            "x = urllib.request.urlopen('http://example.com')\n")
        (pkg / "gone.py").write_text("import urllib.request\n"
                                     "y = 1\n")
        for args in (["git", "init", "-q"],
                     ["git", "add", "-A"],
                     ["git", "-c", "user.email=t@t", "-c",
                      "user.name=t", "commit", "-qm", "seed"]):
            subprocess.run(args, cwd=repo, check=True,
                           capture_output=True)
        subprocess.run(["git", "mv", str(pkg / "bad.py"),
                        str(pkg / "moved.py")], cwd=repo, check=True,
                       capture_output=True)
        subprocess.run(["git", "rm", "-q", str(pkg / "gone.py")],
                       cwd=repo, check=True, capture_output=True)
        proc = subprocess.run(
            [sys.executable, "-m", "tools.osselint", "--changed",
             "--format=json", "--root", str(repo)],
            cwd=ROOT, capture_output=True, text=True, timeout=60)
        assert proc.returncode == 1, proc.stderr
        import json
        payload = json.loads(proc.stdout)
        paths = {f["path"] for f in payload["findings"]}
        assert paths == {
            "open_source_search_engine_tpu/parallel/moved.py"}
        assert {f["rule"] for f in payload["findings"]} \
            == {"urllib-in-parallel"}


class TestCheckGate:
    def test_check_sh_lint_gate_passes_on_tree(self):
        """tools/check.sh --lint-only (tree lint + fixture sanity) is
        the one-command gate; --lint-only stops before the pytest
        slice so this test doesn't recurse into itself."""
        proc = subprocess.run(
            ["bash", str(ROOT / "tools" / "check.sh"), "--lint-only"],
            cwd=ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "lint gate OK" in proc.stdout


class TestRuleMechanics:
    def test_nested_closure_not_flagged_as_blocking(self):
        """A closure DEFINED under a lock runs later — not a
        blocking-under-lock violation."""
        src = ("import time, threading\n"
               "_lock = threading.Lock()\n"
               "def f():\n"
               "    with _lock:\n"
               "        def later():\n"
               "            time.sleep(1)\n"
               "        return later\n")
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/utils/x.py")
        assert [f.rule for f in found] == []

    def test_syntax_error_is_reported_not_raised(self):
        found = osselint.check_source(
            "def broken(:\n", "open_source_search_engine_tpu/x.py")
        assert [f.rule for f in found] == ["syntax-error"]

    def test_device_sync_allowed_at_the_boundary(self):
        src = "import jax\nv = jax.device_get(x)\n"
        assert osselint.check_source(
            src,
            "open_source_search_engine_tpu/query/devindex.py") == []
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/engine.py")
        assert "syntax-error" not in {f.rule for f in found}
        assert [f.rule for f in found] == ["device-sync"]

    def test_device_staging_fenced_only_in_resident_loop(self):
        """device_put/asarray are legal almost everywhere — the
        extended fence applies to query/resident.py alone (its submit
        path must be a pure enqueue)."""
        src = "import jax\nv = jax.device_put(x)\n"
        found = osselint.check_source(
            src, "open_source_search_engine_tpu/query/resident.py")
        assert [f.rule for f in found] == ["device-sync"]
        assert osselint.check_source(
            src, "open_source_search_engine_tpu/query/engine.py") == []
        assert osselint.check_source(
            src,
            "open_source_search_engine_tpu/query/devindex.py") == []
