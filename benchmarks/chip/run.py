#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload paper.scan --seed 7 \\
        --seconds 10 --trace 0

Builds the cell's corpus from the seed, warms every shape its traffic
uses, measures for ``--seconds``, checks the answers against the plain
reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``, each compared number
beside its limit. The same numbers are the last lines on standard error.

It refuses to run without a TPU, or with fewer chips than the cell asks
for: it then exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"run.py: cannot load workload {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    try:
        import repro.core  # noqa: F401  the program under test
    except ImportError as e:
        print(f"run.py: the program's sources are missing ({e})",
              file=sys.stderr)
        return 2
    info = harness.device_info()
    if info["platform"] != "tpu" or info["count"] < cell.chips:
        print(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {info}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"floor {c['floor']}")
        print(f"check {name}: {c['value']} {bound}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
