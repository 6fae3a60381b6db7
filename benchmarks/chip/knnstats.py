"""Program names of the kNN search's device layers, for the metric
readers: fragments of the trace's module names (``jit_<function>``)."""
from __future__ import annotations

# the cascade: cluster probe, WCD bounds, RWMD min-cdist and bounds, the
# fused survivor keep (cluster radius + stage bounds), seed top-k and the
# threshold's sort
PRUNE = ("_probe_dists", "_wcd_bounds", "_rwmd_minm", "_rwmd_rows",
         "_cascade_keep", "_seed_pick", "_threshold")
# candidate solves: the tile's ELL rows, the K gather and the solve
SOLVE = ("_tile_rows", "_gather_g", "_solve_batched_einsum", "sinkhorn")


def per_query_ms(run: dict, fragments) -> float | None:
    """Device ms per answered query in the programs named by
    ``fragments``; None without a trace, a kNN run or such programs."""
    from runstats import module_seconds
    if "step_solved" not in run or not run["queries_done"]:
        return None
    s = module_seconds(run, fragments)
    return None if s is None else 1e3 * s / run["queries_done"]
