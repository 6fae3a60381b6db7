"""Device ms per scored query in the gather and Sinkhorn solve programs."""
from runstats import SOLVE, module_seconds


def read(run):
    s = module_seconds(run, SOLVE)
    if run["kind"] != "scan" or s is None or not run["queries_done"]:
        return None
    return 1e3 * s / run["queries_done"]
