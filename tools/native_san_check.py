"""Sanitizer parity driver — run the native C++ cores under ASan+UBSan.

Builds ``librdbcore.san-<sha>.so`` / ``libdoccore.san-<sha>.so``
(OSSE_NATIVE_SAN=1 artifacts, ``-fsanitize=address,undefined``) and
drives the same parity checks the tier-1 native tests run — merge/searchsorted vs. the numpy
reference, tokenize/hash vs. the Python tokenizer — so any heap
overflow, use-after-free, or UB in ``rdbcore.cpp``/``doccore.cpp``
aborts loudly instead of corrupting an index silently.

The sanitizer runtimes must be loaded BEFORE an uninstrumented Python:
when launched without them this script re-execs itself with
``LD_PRELOAD=libasan.so:libubsan.so`` (paths from
``g++ -print-file-name``) and ``ASAN_OPTIONS=detect_leaks=0`` (CPython
itself "leaks" interned objects at exit; leak mode would drown real
reports).

Deliberately jax-free: only numpy + the host-plane modules import, so
the whole check runs in a couple of seconds.

Usage::

    python -m tools.native_san_check          # re-execs under preload
    OSSE_NATIVE_SAN=1 pytest tests/test_native.py -m slow   # via test
"""

from __future__ import annotations

import os
import subprocess
import sys


def _sanitizer_libs() -> list[str]:
    libs = []
    for name in ("libasan.so", "libubsan.so"):
        out = subprocess.run(["g++", f"-print-file-name={name}"],
                             capture_output=True, text=True,
                             check=False).stdout.strip()
        if out and out != name and os.path.exists(out):
            libs.append(out)
    return libs


def _reexec_under_preload() -> None:
    libs = _sanitizer_libs()
    if not libs:
        print("native_san_check: no sanitizer runtimes found "
              "(g++ -print-file-name) — cannot run", file=sys.stderr)
        sys.exit(2)
    env = dict(os.environ)
    env["LD_PRELOAD"] = ":".join(libs)
    env["OSSE_NATIVE_SAN"] = "1"
    env.setdefault("ASAN_OPTIONS", "detect_leaks=0")
    os.execve(sys.executable,
              [sys.executable, "-m", "tools.native_san_check"], env)


def main() -> int:
    if "libasan" not in os.environ.get("LD_PRELOAD", ""):
        _reexec_under_preload()  # never returns

    os.environ["OSSE_NATIVE_SAN"] = "1"
    import numpy as np

    from open_source_search_engine_tpu import native
    from open_source_search_engine_tpu.index import posdb, rdblite

    assert native.SANITIZE, "OSSE_NATIVE_SAN=1 not honored at import"
    if native.get_lib() is None:
        print("native_san_check: sanitized rdbcore build failed",
              file=sys.stderr)
        return 2

    rng = np.random.default_rng(7)

    def random_run(n, seed):
        r = np.random.default_rng(seed)
        keys = posdb.pack(
            termid=r.integers(0, 60, n), docid=r.integers(0, 300, n),
            wordpos=r.integers(0, 2000, n),
            delbit=(r.random(n) > 0.25).astype(int))
        return keys[rdblite.key_sort_order(keys)]

    checks = 0

    # --- rdbcore: n-way merge parity (both tombstone modes) ------------
    runs = [random_run(int(rng.integers(50, 600)), s)
            for s in range(5)]
    for keep in (False, True):
        nat = native.merge_runs(runs, keep)
        assert nat is not None, "sanitized merge_runs unavailable"
        all_keys = np.concatenate(runs)
        recency = np.concatenate(
            [np.full(len(r), i, np.int64) for i, r in enumerate(runs)])
        ref = all_keys[rdblite._dedup_newest(all_keys, recency, keep)]
        assert len(nat) == len(ref), \
            f"merge length {len(nat)} != {len(ref)} (keep={keep})"
        np.testing.assert_array_equal(
            nat.view(np.uint8).reshape(-1),
            ref.view(np.uint8).reshape(-1))
        checks += 1

    # --- rdbcore: searchsorted parity ----------------------------------
    keys = random_run(800, 99)
    probes = random_run(64, 100)
    for side in ("left", "right"):
        nat = np.array([native.searchsorted(keys, probes[i:i + 1], side)
                        for i in range(len(probes))])
        orig_avail = native.available
        native.available = lambda: False
        try:
            ref = rdblite.searchsorted_keys(keys, probes, side)
        finally:
            native.available = orig_avail
        np.testing.assert_array_equal(nat, ref)
        checks += 1

    # --- doccore: tokenize + hash parity -------------------------------
    if native.get_doccore() is None:
        print("native_san_check: sanitized doccore build failed",
              file=sys.stderr)
        return 2
    from open_source_search_engine_tpu.build import tokenizer
    from open_source_search_engine_tpu.utils import ghash
    html = ("<html><head><title>Sanitizer parity</title>"
            "<meta name=\"description\" content=\"asan ubsan\"></head>"
            "<body><h1>Heading words</h1><p>Body text with "
            "<a href=\"http://example.com/x\">anchor text</a> and "
            "repeated repeated terms.</p>"
            "<script>ignored()</script></body></html>")
    url = "http://example.com/parity"
    os.environ["OSSE_NATIVE_TOKENIZE"] = "0"
    try:
        py = tokenizer.tokenize_html(html, url)
    finally:
        os.environ["OSSE_NATIVE_TOKENIZE"] = "1"
    nat_doc = tokenizer.tokenize_html(html, url)
    cols = getattr(nat_doc, "native", None)
    assert cols is not None, "native tokenize fell back"
    assert py.words == nat_doc.words, "word parity under sanitizers"
    assert py.wordpos == nat_doc.wordpos, \
        "wordpos parity under sanitizers"
    assert py.hashgroups == nat_doc.hashgroups, \
        "hashgroup parity under sanitizers"
    tids = [ghash.term_id(w) for w in nat_doc.words]
    assert tids == [int(t) for t in cols.termid], \
        "termid parity under sanitizers"
    checks += 1
    # ghash.hash64 switches to blake2b above 1 KiB; native parity is
    # the short-key (FNV+avalanche) regime only
    for blob in (b"", b"a", b"hello world", b"ab\x00cd",
                 bytes(range(256)) * 4):
        nat = native.hash64_native(blob)
        assert nat == ghash.hash64(blob), f"hash64 parity: {blob[:8]!r}"
    checks += 1

    print(f"native_san_check: OK ({checks} parity checks clean under "
          "ASan+UBSan)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
