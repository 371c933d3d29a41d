"""Native host core — C++ storage-engine primitives behind ctypes.

The reference's host plane is C++ (SURVEY §2: "everything is C++"); ours
keeps the byte-crunching primitives native too: n-way run merge with
tombstone annihilation, key binary search, and sorted-batch dedup
(``rdbcore.cpp``). Built on demand with g++ into a shared object whose
NAME carries a hash of the source and the flags it was built from
(``librdbcore-<sha>.so``), so a library left over from another version
of the source — the artefacts are untracked, and a copied tree loses
its mtimes — is never loaded: a changed source misses and rebuilds.
Every caller has a vectorized-numpy fallback, so the framework works
(slower) without a toolchain; the degrade is logged and counted
(``native.fallback``) and ``chip_smoke.py`` fails on it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

from ..utils.log import get_logger
from ..utils.stats import g_stats

log = get_logger("native")

#: OSSE_NATIVE_SAN=1 → build/load ASan+UBSan-instrumented natives
#: instead of the optimized ones. Separate ``.san`` artifact names so
#: the two modes never clobber each other's build cache. The sanitizer
#: runtimes must be preloaded into the (uninstrumented) Python process —
#: ``tools/native_san_check.py`` handles the LD_PRELOAD dance.
SANITIZE = os.environ.get("OSSE_NATIVE_SAN") == "1"
_SAN_FLAGS = ["-fsanitize=address,undefined", "-fno-omit-frame-pointer",
              "-g", "-O1"]

_DIR = Path(__file__).parent
_SRC = _DIR / "rdbcore.cpp"
_lock = threading.Lock()
_lib = None
_tried = False


def _load(src: Path, stem: str, opt: str) -> ctypes.CDLL | None:
    """Load the library built from ``src`` as it reads NOW, building
    it first when no artefact carries this source's hash; None (logged
    and counted) when the toolchain or the loader fails."""
    flags = _SAN_FLAGS if SANITIZE else [opt]
    key = hashlib.sha256(
        src.read_bytes() + " ".join(flags).encode()).hexdigest()[:16]
    if SANITIZE:
        stem += ".san"
    so = _DIR / f"{stem}-{key}.so"
    try:
        if not so.exists():
            # build beside the target and rename: several processes
            # (pytest workers) may race here, and none may ever load a
            # half-written file
            tmp = _DIR / f".{so.stem}.{os.getpid()}.so"
            subprocess.run(
                ["g++", *flags, "-shared", "-fPIC", str(src), "-o",
                 str(tmp)], check=True, capture_output=True, timeout=180)
            os.replace(tmp, so)
            for stale in _DIR.glob(f"{stem}-*.so"):
                if stale != so:
                    stale.unlink(missing_ok=True)
        return ctypes.CDLL(str(so))
    except (OSError, subprocess.SubprocessError) as e:
        log.warning("native %s unavailable (fallback in use): %s",
                    stem, e)
        g_stats.count("native.fallback")
        return None


def get_lib():
    """The loaded librdbcore, building it on first use; None = fallback."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib = _load(_SRC, "librdbcore", "-O3")
        if lib is None:
            return None
        lib.osse_merge_runs.restype = ctypes.c_int64
        lib.osse_merge_runs.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p]
        lib.osse_searchsorted.restype = ctypes.c_int64
        lib.osse_searchsorted.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int32]
        _lib = lib
        log.info("librdbcore loaded")
        return _lib


def available() -> bool:
    return get_lib() is not None


def merge_runs(key_arrays: list[np.ndarray],
               keep_tombstones: bool) -> np.ndarray | None:
    """Native n-way merge of sorted structured-key arrays (oldest→newest).
    Returns merged keys, or None if the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    key_dtype = key_arrays[0].dtype
    ks = key_dtype.itemsize
    bufs = [np.ascontiguousarray(a) for a in key_arrays]
    total = sum(len(a) for a in bufs)
    out = np.empty(total, dtype=key_dtype)
    RunPtrs = ctypes.c_void_p * len(bufs)
    runs = RunPtrs(*[b.ctypes.data for b in bufs])
    counts = (ctypes.c_int64 * len(bufs))(*[len(b) for b in bufs])
    n = lib.osse_merge_runs(
        runs, counts, len(bufs), ks, int(keep_tombstones),
        out.ctypes.data)
    return out[:n].copy()


def searchsorted(sorted_keys: np.ndarray, probe: np.ndarray,
                 side: str) -> int | None:
    """Native binary search of one probe key; None if lib unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    a = np.ascontiguousarray(sorted_keys)
    p = np.ascontiguousarray(probe)
    return int(lib.osse_searchsorted(
        a.ctypes.data, len(a), a.dtype.itemsize,
        p.ctypes.data, 1 if side == "right" else 0))


# --- doccore: native HTML tokenize + term hash + rank columns ----------

_DOC_SRC = _DIR / "doccore.cpp"
_doc_lib = None
_doc_tried = False


class _OsseDoc(ctypes.Structure):
    _fields_ = [
        ("n", ctypes.c_int64),
        ("termid", ctypes.POINTER(ctypes.c_uint64)),
        ("wordpos", ctypes.POINTER(ctypes.c_int32)),
        ("hashgroup", ctypes.POINTER(ctypes.c_uint8)),
        ("density", ctypes.POINTER(ctypes.c_uint8)),
        ("spam", ctypes.POINTER(ctypes.c_uint8)),
        ("sentence", ctypes.POINTER(ctypes.c_int32)),
        ("sect", ctypes.POINTER(ctypes.c_uint64)),
        ("nb", ctypes.c_int64),
        ("b_termid", ctypes.POINTER(ctypes.c_uint64)),
        ("b_src", ctypes.POINTER(ctypes.c_int32)),
        # POINTER(c_char), NOT c_char_p: c_char_p field access copies
        # up to the first NUL, and string_at over the declared length
        # would then read past the truncated copy (embedded NULs occur
        # in real crawled pages)
        ("words_buf", ctypes.POINTER(ctypes.c_char)),
        ("words_len", ctypes.c_int64),
        ("text_buf", ctypes.POINTER(ctypes.c_char)),
        ("text_len", ctypes.c_int64),
        ("title_buf", ctypes.POINTER(ctypes.c_char)),
        ("title_len", ctypes.c_int64),
        ("desc_buf", ctypes.POINTER(ctypes.c_char)),
        ("desc_len", ctypes.c_int64),
        ("date_buf", ctypes.POINTER(ctypes.c_char)),
        ("date_len", ctypes.c_int64),
        ("links_buf", ctypes.POINTER(ctypes.c_char)),
        ("links_len", ctypes.c_int64),
        ("nsect", ctypes.c_int64),
        ("sect_hash", ctypes.POINTER(ctypes.c_uint64)),
        ("sect_words", ctypes.POINTER(ctypes.c_int32)),
        ("sect_buf", ctypes.POINTER(ctypes.c_char)),
        ("sect_len", ctypes.c_int64),
        ("fallback", ctypes.c_int32),
    ]


def get_doccore():
    """The loaded libdoccore, building on first use; None = fallback."""
    global _doc_lib, _doc_tried
    with _lock:
        if _doc_lib is not None or _doc_tried:
            return _doc_lib
        _doc_tried = True
        lib = _load(_DOC_SRC, "libdoccore", "-O2")
        if lib is None:
            return None
        lib.osse_tokenize.restype = ctypes.POINTER(_OsseDoc)
        lib.osse_tokenize.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int32]
        lib.osse_doc_free.argtypes = [ctypes.POINTER(_OsseDoc)]
        lib.osse_hash64.restype = ctypes.c_uint64
        lib.osse_hash64.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_uint64]
        _doc_lib = lib
        log.info("libdoccore loaded")
        return _doc_lib


class NativeDocCols:
    """Columnar product of one native tokenize call (numpy copies; the
    C arena is freed before returning)."""

    __slots__ = ("termid", "wordpos", "hashgroup", "density", "spam",
                 "sentence", "sect", "b_termid", "b_src", "words",
                 "text", "title", "desc", "date", "links", "sect_hash",
                 "sect_words", "sect_content")


def _arr(ptr, n, dtype):
    """Copy n elements out of a ctypes pointer — np.frombuffer over the
    raw address (ctypeslib.as_array's per-call type synthesis measured
    ~4× slower at these sizes)."""
    if n == 0:
        return np.empty(0, dtype)
    src = np.dtype(ptr._type_)  # numpy understands ctypes scalar types
    buf = ctypes.string_at(ptr, n * src.itemsize)
    a = np.frombuffer(buf, dtype=src, count=n)
    return a.astype(dtype) if a.dtype != dtype else a.copy()


def tokenize_native(content: str, url: str | None,
                    is_html: bool) -> "NativeDocCols | None":
    """Native tokenize+hash+rank; None when the lib is unavailable."""
    lib = get_doccore()
    if lib is None:
        return None
    cb = content.encode("utf-8", "replace")
    ub = url.encode("utf-8", "replace") if url else b""
    dp = lib.osse_tokenize(cb, len(cb), ub, len(ub), int(is_html))
    try:
        d = dp.contents
        if d.fallback:
            # exotic HTML entity outside the native table: the Python
            # tokenizer (full HTML5 charref set) must own this doc so
            # both paths stay bit-identical
            return None
        out = NativeDocCols()
        n = int(d.n)
        out.termid = _arr(d.termid, n, np.uint64)
        out.wordpos = _arr(d.wordpos, n, np.int64)
        out.hashgroup = _arr(d.hashgroup, n, np.uint64)
        out.density = _arr(d.density, n, np.uint64)
        out.spam = _arr(d.spam, n, np.uint64)
        out.sentence = _arr(d.sentence, n, np.int64)
        out.sect = _arr(d.sect, n, np.uint64)
        nb = int(d.nb)
        out.b_termid = _arr(d.b_termid, nb, np.uint64)
        out.b_src = _arr(d.b_src, nb, np.int64)
        wb = ctypes.string_at(d.words_buf, d.words_len)
        out.words = wb.decode("utf-8", "replace").split("\n") if wb \
            else []
        out.text = ctypes.string_at(d.text_buf, d.text_len).decode(
            "utf-8", "replace")
        out.title = ctypes.string_at(d.title_buf, d.title_len).decode(
            "utf-8", "replace")
        out.desc = ctypes.string_at(d.desc_buf, d.desc_len).decode(
            "utf-8", "replace")
        out.date = ctypes.string_at(d.date_buf, d.date_len).decode(
            "utf-8", "replace")
        lb = ctypes.string_at(d.links_buf, d.links_len).decode(
            "utf-8", "replace")
        out.links = []
        if lb:
            for rec in lb.split("\x1e"):
                href, _, anchor = rec.partition("\x1f")
                out.links.append((href, anchor))
        ns = int(d.nsect)
        out.sect_hash = _arr(d.sect_hash, ns, np.uint64)
        out.sect_words = _arr(d.sect_words, ns, np.int64)
        sb = ctypes.string_at(d.sect_buf, d.sect_len).decode(
            "utf-8", "replace")
        out.sect_content = sb.split("\x1e") if sb else []
        return out
    finally:
        lib.osse_doc_free(dp)


def hash64_native(data: bytes, seed: int = 0) -> int | None:
    lib = get_doccore()
    if lib is None:
        return None
    return int(lib.osse_hash64(data, len(data), seed))
