"""Share of the candidate tiles' (query, doc slot) pairs that were
padding, in %: 1 - ``pairs_solved`` / ``slots_solved`` (program
counters)."""


def read(run):
    stats = run.get("host_stats")
    if not stats or not stats.get("slots_solved"):
        return None
    return 100.0 * (1.0 - stats["pairs_solved"] / stats["slots_solved"])
