"""Share of the (query, doc) pairs of the corpus that search solved
exactly, in %: ``pairs_solved`` over queries x docs (program counters)."""


def read(run):
    stats = run.get("host_stats")
    if not stats or "pairs_solved" not in stats:
        return None
    pairs = stats["search_queries"] * run["n_docs"]
    return 100.0 * stats["pairs_solved"] / pairs if pairs else None
