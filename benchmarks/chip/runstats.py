"""Device seconds of named modules, for the metric readers."""
from __future__ import annotations

# module-name fragments of each layer's device programs, as the trace
# names them (``jit_<function>``)
KBLOCK = ("_compute_kq", "assemble_kq", "_cdist_rows")
SOLVE = ("_gather_g", "_solve_batched_einsum", "sinkhorn")


def module_seconds(run: dict, fragments) -> float | None:
    trace = run.get("trace")
    if trace is None:
        return None
    hit = [s for name, s in trace.modules.items()
           if any(f in name for f in fragments)]
    return sum(hit) if hit else None
