"""``warm_f1_s`` (dispatch): seconds start-up spent dispatching the closed F1
program set once (the program's span ``devindex.warm_f1``: a compile or a
cache load a program). It lies in set-up, before the window's counters begin,
so it is read from the program's own ``g_stats``, whole. A program without the
span: nothing to read."""


def read(ctx: dict) -> float | None:
    try:
        from open_source_search_engine_tpu.utils.stats import g_stats
    except ImportError:
        return None
    lat = g_stats.snapshot()["latencies"].get("devindex.warm_f1")
    if not lat or not lat["count"]:
        return None
    return lat["avg_ms"] * lat["count"] / 1000.0
