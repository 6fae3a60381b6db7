"""Queries fully scored against the whole corpus per second, over the
whole closed-loop window."""


def read(run):
    if run["kind"] != "scan" or run["window_s"] <= 0:
        return None
    return run["queries_done"] / run["window_s"]
