"""Device ingest-plane tests — bit-exactness against the host oracle.

The host NumPy pipeline in ``devindex._build_base``/``_build_delta`` is
the parity oracle for ``build/devbuild.py`` (same role the host-merge
path plays for mesh serving): every derived base column, directory
table and f16 impact must match *bitwise*, across corpora that exercise
tombstone annihilation, the ``occ < P`` store cap and multi-run merges.
"""

import numpy as np
import pytest

from open_source_search_engine_tpu.build import devbuild, docproc
from open_source_search_engine_tpu.index.collection import Collection
from open_source_search_engine_tpu.query.devindex import DeviceIndex
from open_source_search_engine_tpu.utils import ghash
from open_source_search_engine_tpu.utils.stats import g_stats


def _mkdoc(rng, words, i, repeat=None):
    n = int(rng.integers(20, 160))
    toks = list(rng.choice(words, size=n))
    if repeat is not None:
        # one term far past the positions-per-(term,doc) store cap
        toks += [repeat] * 30
    return (f"http://h{i % 17}.example.com/p{i}",
            f"<html><title>{' '.join(rng.choice(words, size=4))}</title>"
            f"<body><p>{' '.join(toks)}</p></body></html>")


def _seed_corpus(tmp_path, seed, name="pb"):
    """Multi-run corpus with tombstones, re-adds and an over-cap term."""
    rng = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(300)]
    c = Collection(name, tmp_path / f"{name}{seed}")
    docs = [_mkdoc(rng, words, i) for i in range(100)]
    docs[7] = _mkdoc(rng, words, 7, repeat="capstone")
    docproc.index_batch(c, docs[:60])
    c.posdb.dump()
    c.titledb.dump()
    docproc.index_batch(c, docs[60:90])
    c.posdb.dump()
    # run 3: tombstones for docs living in runs 1 and 2, plus a re-add
    # (annihilation must collapse across run boundaries, newest wins)
    docproc.remove_document(c, docs[3][0])
    docproc.remove_document(c, docs[65][0])
    docproc.index_document(c, *docs[5])
    c.posdb.dump()
    return c, docs


_BASE_COLS = ("d_payload", "d_docc", "d_doc", "d_rs", "d_cnt",
              "d_siterank", "d_doclang", "d_cube", "d_dense_rs",
              "d_dense_cnt")


def _assert_columns_equal(host, dev):
    for name in ("dir_termids", "base_df", "dir_dstart", "dir_pstart",
                 "base_docids", "h_doc_col"):
        assert np.array_equal(getattr(host, name), getattr(dev, name)), name
    assert (host.Nb, host.Mb, host.N2, host.M2, host.D_cap) == \
           (dev.Nb, dev.Mb, dev.N2, dev.M2, dev.D_cap)
    for name in _BASE_COLS:
        a, b = np.asarray(getattr(host, name)), np.asarray(getattr(dev, name))
        assert a.shape == b.shape and np.array_equal(a, b), name
    # impacts compare as raw f16 bit patterns: the demotion rounding is
    # part of the contract, not an approximation
    for name in ("d_imp", "d_dense_imp"):
        a = np.asarray(getattr(host, name)).view(np.uint16)
        b = np.asarray(getattr(dev, name)).view(np.uint16)
        assert np.array_equal(a, b), name


class TestBaseBitExact:
    @pytest.mark.parametrize("seed", [7, 23, 101])
    def test_device_base_matches_host_oracle(self, tmp_path, monkeypatch,
                                             seed):
        c, _ = _seed_corpus(tmp_path, seed)
        # device first: the device plane never writes the disk cache, so
        # the host build below derives from scratch (a cache hit would
        # make this test compare the cache against itself)
        monkeypatch.setenv("OSSE_DEVBUILD", "1")
        before = g_stats.counters.get("build.devbuild_fallback", 0)
        built = g_stats.counters.get("build.device_base", 0)
        dev = DeviceIndex(c)
        # the base was built by the device plane, and by nothing else
        assert g_stats.counters.get("build.devbuild_fallback", 0) == before
        assert g_stats.counters.get("build.device_base", 0) == built + 1
        monkeypatch.setenv("OSSE_DEVBUILD", "0")
        host = DeviceIndex(c)
        assert host._base_fp == dev._base_fp
        _assert_columns_equal(host, dev)

    def test_store_cap_applied(self, tmp_path, monkeypatch):
        """The over-cap doc keeps exactly P positions of the repeated
        term on both paths (occ < P store cap)."""
        c, _ = _seed_corpus(tmp_path, 7, name="cap")
        monkeypatch.setenv("OSSE_DEVBUILD", "1")
        dev = DeviceIndex(c)
        tid = ghash.term_id("capstone")
        i = int(np.searchsorted(dev.dir_termids, np.uint64(tid)))
        assert dev.dir_termids[i] == np.uint64(tid)
        d0, d1 = int(dev.dir_dstart[i]), int(dev.dir_dstart[i + 1])
        assert d1 - d0 == 1  # one (term, doc) pair
        p0, p1 = int(dev.dir_pstart[i]), int(dev.dir_pstart[i + 1])
        assert p1 - p0 == dev.P  # 30 occurrences capped to P stored


class TestDeltaFold:
    QUERIES = ["w1", "w2 w3", '"w4 w5"', "w1 -w2", "capstone"]

    def test_delta_fold_equals_full_rebuild(self, tmp_path, monkeypatch):
        """Folding unflushed writes as a device delta tile must rank
        identically to dumping them and rebuilding the base."""
        monkeypatch.setenv("OSSE_DEVBUILD", "1")
        rng = np.random.default_rng(31)
        words = [f"w{i}" for i in range(120)]
        c, docs = _seed_corpus(tmp_path, 31, name="df")
        folded = DeviceIndex(c)
        # unflushed writes: adds + a tombstone for a base doc
        extra = [_mkdoc(rng, words, 1000 + i) for i in range(20)]
        docproc.index_batch(c, extra)
        docproc.remove_document(c, docs[10][0])
        before = g_stats.counters.get("build.device_delta", 0)
        deltas = folded.delta_rebuilds
        assert folded.refresh()
        assert folded.delta_rebuilds == deltas + 1
        assert folded.full_rebuilds == 1  # the fold never rebuilt the base
        assert g_stats.counters.get("build.device_delta", 0) == before + 1
        # oracle: dump the memtable and full-rebuild from the runs
        c.posdb.dump()
        c.titledb.dump()
        rebuilt = DeviceIndex(c)
        assert rebuilt.full_rebuilds == 1
        for q in self.QUERIES:
            a = folded.search(q, topk=32)
            b = rebuilt.search(q, topk=32)
            assert a[2] == b[2], q
            ka = sorted(zip([round(float(s), 3) for s in a[1][:a[2]]],
                            a[0][:a[2]]))
            kb = sorted(zip([round(float(s), 3) for s in b[1][:b[2]]],
                            b[0][:b[2]]))
            assert ka == kb, q

    def test_delta_matches_host_delta(self, tmp_path, monkeypatch):
        """Device delta columns bit-exact vs the host delta oracle."""
        c, docs = _seed_corpus(tmp_path, 57, name="dh")
        rng = np.random.default_rng(57)
        words = [f"w{i}" for i in range(120)]
        extra = [_mkdoc(rng, words, 2000 + i) for i in range(15)]

        monkeypatch.setenv("OSSE_DEVBUILD", "1")
        dev = DeviceIndex(c)
        docproc.index_batch(c, extra)
        docproc.remove_document(c, docs[11][0])
        assert dev.refresh()

        monkeypatch.setenv("OSSE_DEVBUILD", "0")
        host = DeviceIndex(c)

        for name in ("dir2_termids", "delta_df", "dir2_dstart",
                     "dir2_pstart", "all_docids"):
            assert np.array_equal(getattr(host, name), getattr(dev, name)), \
                name
        _assert_columns_equal(host, dev)
        assert np.array_equal(np.asarray(host.d_dead),
                              np.asarray(dev.d_dead))


class TestCacheSwap:
    def test_crash_during_save_keeps_old_cache(self, tmp_path, monkeypatch):
        """Regression: the stale-fingerprint unlink must happen AFTER
        the new cache file lands — a crash mid-save used to leave no
        cache at all, forcing a full rebuild on next boot."""
        monkeypatch.setenv("OSSE_DEVBUILD", "0")  # host path writes cache
        c, _ = _seed_corpus(tmp_path, 13, name="cs")
        idx = DeviceIndex(c)
        old_cache = idx._cache_path(idx._base_fp)
        assert old_cache.exists()

        # run-set moves → new fingerprint; crash while saving its cache
        docproc.index_batch(c, [("http://x.example.com/new",
                                 "<html><body><p>fresh words here"
                                 "</p></body></html>")])
        c.posdb.dump()

        def boom(*a, **k):
            raise OSError("disk full")
        monkeypatch.setattr(np, "savez", boom)
        with pytest.raises(OSError):
            DeviceIndex(c)
        # the old fingerprint's cache must have survived the crash
        assert old_cache.exists()

        monkeypatch.undo()
        monkeypatch.setenv("OSSE_DEVBUILD", "0")
        idx2 = DeviceIndex(c)
        new_cache = idx2._cache_path(idx2._base_fp)
        assert new_cache.exists()
        assert not old_cache.exists()  # stale fingerprint reaped
