"""Device ms per scored query in the K-block programs (the stacked cdist
GEMM and its exp)."""
from runstats import KBLOCK, module_seconds


def read(run):
    s = module_seconds(run, KBLOCK)
    if run["kind"] != "scan" or s is None or not run["queries_done"]:
        return None
    return 1e3 * s / run["queries_done"]
