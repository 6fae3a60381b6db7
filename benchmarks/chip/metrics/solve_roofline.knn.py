"""The candidate solves' share of their roofline, in %: the least time
the chip's peaks allow for the solves' work (``work.call_work``, counted
from each step's live queries' word counts and its solved candidates'
count and word total, padding excluded) over the device time of the
tile row gathers, K gathers and solves."""
from knnstats import SOLVE
from runstats import module_seconds
from work import Work, call_work, roofline_seconds


def read(run):
    if "step_solved" not in run or run["peaks"] is None:
        return None
    s = module_seconds(run, SOLVE)
    if s is None:
        return None
    total = Work(0.0, 0.0)
    for v_r, n, nnz in zip(run["step_vr"], run["step_solved"],
                           run["step_solved_nnz"]):
        total += call_work(v_r, nnz, n, run["n_iter"])
    least, _ = roofline_seconds(total, run["peaks"]["flops_per_s"],
                                run["peaks"]["bytes_per_s"])
    return 100.0 * least / s
