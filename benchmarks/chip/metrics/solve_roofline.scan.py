"""The gather + solve programs' share of their roofline, in %: the least
time the chip's peaks allow for the solves' work (``work.call_work``,
counted from the queries' and docs' word counts, padding excluded) over
their device time. Each step is one call against every doc."""
from runstats import SOLVE, module_seconds
from work import Work, call_work, roofline_seconds


def read(run):
    s = module_seconds(run, SOLVE)
    if run["kind"] != "scan" or s is None or run["peaks"] is None:
        return None
    total = Work(0.0, 0.0)
    for v_r in run["step_vr"]:
        total += call_work(v_r, run["docs_nnz_sum"], run["n_docs"],
                           run["n_iter"])
    least, _ = roofline_seconds(total, run["peaks"]["flops_per_s"],
                                run["peaks"]["bytes_per_s"])
    return 100.0 * least / s
