"""``tail_fetches`` (results tail): titledb reads a query, over the window
(``query.titlerec_fetch``, a ``get_document`` call the batch path's tail made
past its memo, over ``query``). A page shows ten rows, so ten is the floor
where nothing repeats. A program without the counter: nothing to read."""


def read(ctx: dict) -> float | None:
    c = ctx["counters"]
    queries = c.get("query", 0)
    if "query.titlerec_fetch" not in c or not queries:
        return None
    return c["query.titlerec_fetch"] / queries
