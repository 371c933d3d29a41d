"""Device telemetry plane (utils/devwatch.py): the HBM ledger across
park/promote and delta-fold lifecycles, the wave flight recorder's
bounded ring and issue→wait→collect split, roofline attribution from
``cost_analysis()`` per (kernel, shape bucket), the OSSE_DEVWATCH=0
true-no-op contract, and the /admin/hbm + /admin/device pages.

Reference: Stats.cpp's performance graph + PageStats/PagePerf in the
ancestor — host-side observability this plane moves to the device
boundary.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from open_source_search_engine_tpu.build import docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query import engine
from open_source_search_engine_tpu.query.engine import (get_device_index,
                                                        get_resident_loop)
from open_source_search_engine_tpu.serve.server import SearchHTTPServer
from open_source_search_engine_tpu.serve.tenancy import ResidencyManager
from open_source_search_engine_tpu.utils import devwatch
from open_source_search_engine_tpu.utils.membudget import g_membudget
from open_source_search_engine_tpu.utils.stats import g_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a wave's four clock readings, as the resident loop hands them over:
#: issue began, issue done, collect began, collect done
MARKS = (10.0, 10.25, 10.75, 12.0)

DOC = ("<html><head><title>{t}</title></head><body>"
       "<p>walrus {t} herd gathers on the {t} shore. "
       "The walrus colony of {t} dives deep.</p></body></html>")


def _mk_coll(tmp_path, name: str, docs: int = 1) -> Collection:
    c = Collection(name, tmp_path)
    c.conf.pqr_enabled = False
    for i in range(docs):
        docproc.index_document(c, f"http://{name}.test/p{i}",
                               DOC.format(t=f"{name}{i}"))
    return c


@pytest.fixture(autouse=True)
def _devwatch_reset():
    """devwatch is a process-wide singleton; every test starts and
    ends with the plane disarmed and empty."""
    devwatch.disable()
    devwatch.reset()
    g_stats.reset()
    yield
    devwatch.disable()
    devwatch.reset()
    g_membudget.set_label_cap("device", 0)


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

class TestLedger:
    def test_register_replace_release(self):
        devwatch.enable()
        devwatch.note_columns("ca", "devindex", {"doc": 100, "imp": 50})
        assert devwatch.collection_bytes("ca") == 150
        # re-noting a (coll, plane) REPLACES the slice — a refresh
        # must not leak the previous generation's columns
        devwatch.note_columns("ca", "devindex", {"doc": 200})
        assert devwatch.collection_bytes("ca") == 200
        devwatch.note_buffer("ca", "mesh_stage", "wave1", 30)
        assert devwatch.collection_bytes("ca") == 230
        devwatch.drop_buffer("ca", "mesh_stage", "wave1")
        assert devwatch.collection_bytes("ca") == 200
        devwatch.note_columns("cb", "devindex", {"doc": 10})
        assert devwatch.g_devwatch.total_bytes() == 210
        devwatch.drop("ca")  # every plane dies with the collection
        assert devwatch.collection_bytes("ca") == 0
        assert devwatch.g_devwatch.total_bytes() == 10
        # the plane gauges follow the ledger
        assert g_stats.snapshot()["gauges"]["hbm.devindex.bytes"] == 10
        assert g_stats.snapshot()["gauges"]["hbm.total.bytes"] == 10

    def test_disabled_records_nothing(self):
        devwatch.note_columns("ca", "devindex", {"doc": 100})
        assert devwatch.collection_bytes("ca") == 0
        assert devwatch.wave_begin("test") is None
        snap = devwatch.snapshot()
        assert snap["enabled"] is False
        assert snap["ledger"] == {} and snap["waves"] == []

    def test_reconcile_null_safe_on_cpu(self):
        devwatch.enable()
        devwatch.note_columns("ca", "devindex", {"doc": 100})
        rec = devwatch.reconcile()
        assert rec["ledger_bytes"] == 100
        for d in rec["devices"]:  # CPU: memory_stats() is None
            assert d["bytes_in_use"] is None or d["bytes_in_use"] >= 0
        json.dumps(rec)  # admin/json-serializable

    def test_delta_fold_lifecycle_tracks_resident_bytes(self, tmp_path):
        devwatch.enable()
        coll = _mk_coll(tmp_path, "dfl", docs=2)
        di = get_device_index(coll)
        assert devwatch.collection_bytes("dfl") == di.resident_bytes()
        docproc.index_document(coll, "http://dfl.test/extra",
                               DOC.format(t="extra"))
        # drop the slice by hand: the fold must RE-note it — proof the
        # refresh path re-registers every generation, not just boot
        devwatch.drop("dfl")
        assert di.refresh() is True
        assert devwatch.collection_bytes("dfl") == di.resident_bytes()
        assert devwatch.collection_bytes("dfl") > 0

    def test_park_releases_promote_reregisters(self, tmp_path):
        devwatch.enable()
        rm = ResidencyManager(max_resident=1)
        try:
            ca = _mk_coll(tmp_path, "pka")
            cb = _mk_coll(tmp_path, "pkb")
            rm.loop_for(ca)
            na = devwatch.collection_bytes("pka")
            assert na > 0
            rm.loop_for(cb)  # parks pka (LRU) → ledger drops the slice
            assert devwatch.collection_bytes("pka") == 0
            assert devwatch.collection_bytes("pkb") > 0
            rm.loop_for(ca)  # re-promotion re-registers, bit-identical
            assert devwatch.collection_bytes("pka") == na
        finally:
            rm.stop_all()


# ---------------------------------------------------------------------------
# wave flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        devwatch.enable()
        for _ in range(devwatch.RING + 40):
            devwatch.wave_end(devwatch.wave_begin("test"), MARKS)
        snap = devwatch.snapshot()
        assert len(snap["waves"]) == devwatch.RING
        assert snap["totals"]["waves"] == devwatch.RING + 40

    def test_resident_waves_record_the_split(self, tmp_path):
        devwatch.enable()
        coll = _mk_coll(tmp_path, "fr", docs=3)
        loop = get_resident_loop(coll)
        plan = engine._compile_cached("walrus", 0)
        for _ in range(3):
            loop.submit([plan], topk=8).wait(timeout=120)
        snap = devwatch.snapshot()
        waves = [w for w in snap["waves"] if w["source"] == "resident"]
        assert waves
        w = waves[-1]
        for k in ("issue_s", "wait_s", "collect_s", "total_s"):
            assert w[k] >= 0.0
        assert w["error"] is None
        assert w["rounds"], "collect must attach at least one round"
        r = w["rounds"][0]
        assert r["device_s"] >= 0.0 and r["bytes_out"] > 0
        assert "escalations" in r

    def test_the_split_is_the_callers_marks(self):
        """devwatch reads no clock for a wave: the record's split is
        built from the four readings the resident loop's own spans
        made (one clock a boundary), handed over at ``wave_end``."""
        devwatch.enable()
        obs = devwatch.wave_begin("test")
        devwatch.wave_issued(obs, plans=2)
        devwatch.wave_collect(obs)
        devwatch.wave_end(obs, MARKS)
        w = devwatch.snapshot()["waves"][-1]
        assert (w["issue_s"], w["wait_s"], w["collect_s"],
                w["total_s"]) == (0.25, 0.5, 1.25, 2.0)
        assert w["plans"] == 2

    def test_error_wave_is_recorded(self):
        devwatch.enable()
        obs = devwatch.wave_begin("test", coll="x")
        devwatch.wave_end(obs, MARKS, error="BoomError")
        snap = devwatch.snapshot()
        assert snap["waves"][-1]["error"] == "BoomError"
        assert snap["totals"]["wave_errors"] == 1


# ---------------------------------------------------------------------------
# roofline attribution
# ---------------------------------------------------------------------------

class TestRoofline:
    def test_first_dispatch_pays_later_ones_count(self):
        devwatch.enable()
        calls = []

        class _Fake:
            def cost_analysis(self):
                return [{"flops": 1000.0, "bytes accessed": 10.0}]

        def thunk():
            calls.append(1)
            return _Fake()

        devwatch.note_cost("kern", (4, 8), thunk, modeled_bytes=9)
        devwatch.note_cost("kern", (4, 8), thunk)
        devwatch.note_cost("kern", (8, 8), thunk)
        assert len(calls) == 2  # one compile per bucket, dict hit after
        roofs = devwatch.snapshot()["rooflines"]
        assert len(roofs) == 2
        ent = next(e for e in roofs if e["bucket"] == [4, 8])
        assert ent["dispatches"] == 2 and ent["modeled_bytes"] == 9
        assert ent["flops"] == 1000.0 and ent["bytes"] == 10.0
        assert ent["verdict"] in ("bandwidth-bound", "compute-bound")

    def test_cost_error_degrades_to_unknown(self):
        devwatch.enable()

        def bad_thunk():
            raise RuntimeError("no cost analysis here")

        devwatch.note_cost("kern", (2,), bad_thunk)
        ent = devwatch.snapshot()["rooflines"][0]
        assert ent["verdict"] == "unknown"
        assert g_stats.snapshot()["counters"]["devwatch.cost_errors"] == 1

    @pytest.mark.parametrize("kind,label", [
        ("TPU v5 lite", "tpu-v5e"), ("TPU v5e", "tpu-v5e"),
        ("TPU v5p", "tpu-v5p"), ("TPU v4", "tpu-v4")])
    def test_peaks_row_matches_device_kind(self, kind, label):
        assert devwatch.peaks_row(kind)[3] == label

    def test_unmatched_device_kind_is_an_error(self):
        """An accelerator outside the table never gets another
        chip's (or the host's assumed) peaks."""
        with pytest.raises(LookupError, match="TPU v9000"):
            devwatch.peaks_row("TPU v9000")

    def test_real_query_populates_a_bucket(self, tmp_path):
        devwatch.enable()
        coll = _mk_coll(tmp_path, "rf", docs=3)
        loop = get_resident_loop(coll)
        plan = engine._compile_cached("walrus herd", 0)
        loop.submit([plan], topk=8).wait(timeout=120)
        loop.submit([plan], topk=8).wait(timeout=120)
        roofs = devwatch.snapshot()["rooflines"]
        assert any(e["kernel"].startswith("devindex.") for e in roofs)
        ent = next(e for e in roofs
                   if e["kernel"].startswith("devindex."))
        assert ent["flops"] > 0 and ent["bytes"] > 0
        assert ent["dispatches"] >= 2
        # an entry, costed, for EVERY (program, bucket) the index's own
        # count says it dispatched: no wave program rides unmodelled
        di = get_device_index(coll)
        costed = {(e["kernel"], tuple(e["bucket"])): e for e in roofs}
        assert di.dispatches and set(di.dispatches) <= set(costed)
        for key, n in di.dispatches.items():
            e = costed[key]
            assert e["dispatches"] == n, key
            assert e["flops"] is not None and e["bytes"] is not None, key


# ---------------------------------------------------------------------------
# the backend doctor (tools/devdoctor.py)
# ---------------------------------------------------------------------------

class TestDoctor:
    def test_record_names_the_backend_and_a_cpu_is_no_accelerator(
            self, monkeypatch, capsys):
        """The record every backend report is built from: platform,
        kind, count, jax version, topology, null-safe memory_stats. On
        this CPU with JAX_PLATFORMS=cpu the verdict is the benign
        ``no-accelerator`` (exit 2); the same backend where the
        environment promises a TPU is a ``fallback`` (exit 1)."""
        import jax

        from tools import devdoctor
        rec = devdoctor.stamp()
        assert rec == devdoctor.stamp() == devdoctor.probe()
        d0 = jax.devices()[0]
        assert (rec["platform"], rec["device_kind"], rec["device_count"],
                rec["jax_version"]) == (
            d0.platform, d0.device_kind, len(jax.devices()),
            jax.__version__)
        assert rec["memory_stats"] is None or all(
            isinstance(v, int) for v in rec["memory_stats"].values())
        assert len(rec["topology"]["devices"]) == min(
            rec["device_count"], 16)
        json.dumps(rec)

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert devdoctor.probe()["doctor"] == "no-accelerator"
        assert devdoctor.main() == devdoctor.EXIT_NO_ACCEL
        assert json.loads(capsys.readouterr().out)["platform"] == "cpu"
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert devdoctor.probe()["doctor"] == "fallback"
        assert devdoctor.main() == devdoctor.EXIT_FALLBACK


# ---------------------------------------------------------------------------
# OSSE_DEVWATCH=0 — true no-op
# ---------------------------------------------------------------------------

class TestNoop:
    @pytest.mark.slow
    def test_subprocess_off_is_true_noop(self):
        code = (
            "import os\n"
            "from open_source_search_engine_tpu.utils import devwatch\n"
            "devwatch.maybe_enable()\n"
            "assert not devwatch.enabled()\n"
            "devwatch.note_columns('c', 'devindex', {'doc': 1})\n"
            "devwatch.note_round(coll='c')\n"
            "devwatch.note_cost('k', (1,), lambda: 1/0)\n"
            "obs = devwatch.wave_begin('t')\n"
            "assert obs is None\n"
            "devwatch.wave_issued(obs); devwatch.wave_collect(obs)\n"
            "devwatch.wave_end(obs, (0.0,) * 4)\n"
            "s = devwatch.snapshot()\n"
            "assert s['enabled'] is False and s['ledger'] == {}\n"
            "assert s['waves'] == [] and s['rooflines'] == []\n"
            "print('NOOP-OK')\n")
        env = dict(os.environ)
        env.update({"OSSE_DEVWATCH": "0", "JAX_PLATFORMS": "cpu"})
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=ROOT, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr
        assert "NOOP-OK" in p.stdout

    def test_disabled_calls_are_cheap(self):
        # a sanity bound that the off path stays a few branches (what
        # the plane costs a served wave is the chip's to say: PERF.md)
        t0 = time.perf_counter()
        for _ in range(20000):
            devwatch.note_round(coll="c", device_s=0.0)
            devwatch.wave_end(devwatch.wave_begin("t"), MARKS)
        assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# admin pages
# ---------------------------------------------------------------------------

def _get(srv, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{srv._httpd.server_port}{path}",
        timeout=30)


class TestAdminPages:
    @pytest.fixture
    def srv(self, tmp_path):
        devwatch.enable()
        s = SearchHTTPServer(tmp_path, port=0)
        coll = s.colldb.get("main")
        coll.conf.pqr_enabled = False
        for i in range(3):
            docproc.index_document(coll, f"http://m.test/p{i}",
                                   DOC.format(t=f"m{i}"))
        s.start()
        yield s
        s.stop()

    def test_hbm_page_and_json(self, srv):
        _get(srv, "/search?q=walrus&format=json").read()
        html = _get(srv, "/admin/hbm").read().decode()
        assert "HBM ledger" in html and "reconciliation" in html
        assert "devindex" in html  # the main collection's slice
        js = json.loads(_get(srv, "/admin/hbm?format=json").read())
        assert js["enabled"] is True
        assert js["total_bytes"] == sum(js["collections"].values())
        assert "reconcile" in js and "planes" in js

    def test_device_page_and_json(self, srv):
        _get(srv, "/search?q=walrus&format=json").read()
        html = _get(srv, "/admin/device").read().decode()
        assert "wave waterfall" in html and "roofline" in html
        js = json.loads(_get(srv, "/admin/device?format=json").read())
        assert js["enabled"] is True
        assert js["totals"]["waves"] >= 1
        assert js["waves"] and js["rooflines"]
        assert "ridge" in js["peaks"] or "label" in js["peaks"]
        # the index's own dispatch counts, by (program, shape bucket)
        assert "dispatches per (program, shape bucket)" in html
        # (start-up dispatched the closed F1 set once; the query rode
        # one of its programs again)
        ds = [d for d in js["dispatches"] if d["coll"] == "main"]
        d = max(ds, key=lambda d: d["dispatches"])
        assert d["dispatches"] >= 2
        assert js["routes"]["main"]["f1"] >= 1
        assert "first route per query" in html
        assert d["program"] == "devindex._two_phase"
        assert d["dispatches"] >= 1 and len(d["bucket"]) == 6

    def test_perf_page_carries_hbm_row(self, srv):
        js = json.loads(_get(srv, "/admin/perf?format=json").read())
        assert "hbm" in js and js["hbm"]["enabled"] is True
        html = _get(srv, "/admin/perf").read().decode()
        assert "/admin/hbm" in html and "/admin/device" in html

    def test_metrics_export_hbm_series(self, srv):
        _get(srv, "/search?q=walrus&format=json").read()
        text = _get(srv, "/metrics").read().decode()
        assert "# TYPE osse_hbm_bytes gauge" in text
        assert 'osse_hbm_bytes{collection="main",plane="devindex"}' \
            in text
