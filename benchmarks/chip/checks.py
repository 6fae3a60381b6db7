"""The comparison that decides ``correct``.

Each cell's limits are in ``limits/<workload>.json``, one number per
quantity compared. Every quantity is "lower is better" and passes when
it is at most its limit:

- ``dist_rel_err``: the widest relative gap between a distance the
  timed path produced and the plain reference's for the same (query,
  doc) pair, over the sampled answers;
- ``nonfinite``: distances that are NaN or infinite;
- ``checked``: how many answers were compared; it has a floor, not a
  limit, so that a run that compares nothing is not correct.
"""
from __future__ import annotations

import numpy as np


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.size == 0:
        return 0.0
    err = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)
    err = np.where(np.isfinite(got), err, np.inf)
    return float(np.max(err))


def compare_scan(rows: dict, ref: dict) -> dict:
    """``rows``/``ref``: {query: (N,) distances} of the program and the
    reference."""
    keys = sorted(rows)
    got = np.stack([rows[q] for q in keys]) if keys else np.zeros((0, 0))
    want = np.stack([ref[q] for q in keys]) if keys else np.zeros((0, 0))
    return {"dist_rel_err": rel_err(got, want),
            "nonfinite": int(np.sum(~np.isfinite(got))),
            "checked": len(keys)}


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every reading at most its
    limit, and ``checked`` at least its floor."""
    out, ok = {}, True
    for name, value in readings.items():
        if name == "checked":
            floor = limits.get("checked_min", 1)
            out[name] = {"value": value, "floor": floor}
            ok &= value >= floor
            continue
        limit = limits[name]
        out[name] = {"value": value, "limit": limit}
        ok &= bool(value <= limit)
    return bool(ok), out
