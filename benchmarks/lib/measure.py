"""The yardstick's arithmetic: peaks, needed bytes, percentiles."""

from __future__ import annotations

#: published peaks by ``device_kind`` (Google Cloud documentation, "TPU
#: v5e"). A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                "hbm_bytes": 16e9},
}

POSTING_BYTES = 8       # a posting as the algorithm needs it: page + payload
TOPK_BYTES = 8          # a result: page + score


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise LookupError(f"no published peaks for device {device_kind!r}")
    return PEAKS[device_kind]


def needed_bytes(queries: list[str], postings_per_word, page: int) -> int:
    """The bytes the queries' answers need from memory whatever implements the
    wave: each query reads each of its words' posting lists once, at
    POSTING_BYTES a posting, and writes its top ``page``."""
    total = 0
    for q in queries:
        words = {int(t[4:]) for t in q.split()}
        total += sum(int(postings_per_word[w]) for w in words) * POSTING_BYTES
        total += page * TOPK_BYTES
    return total


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values (q in 0..100)."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = max(0, min(len(v) - 1, -(-len(v) * q // 100) - 1))
    return v[int(k)]
