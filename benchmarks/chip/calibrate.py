#!/usr/bin/env python3
"""Readings that the limits in ``limits/<workload>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload paper.scan \\
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 3

For each seed, one short run of the cell as it is timed (its traffic,
its sizes, its entry point), compared with the plain reference: first
the program as configured (the lower readings), then the control, the
same program with its bfloat16 path switched on (``precision="bf16"``,
the nearest precision below the configured float32; the upper
readings). One process, so the compile cache is shared. Each run's
checks are printed as one JSON line on standard output. Not part of
the benchmark's own runs; it needs a TPU.
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

CONTROL = {"precision": "bf16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    info = harness.device_info()
    if info["platform"] != "tpu":
        print(f"calibrate.py: needs a TPU; JAX found {info}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    counter = harness.CompileCounter()
    plan = [(int(s), "program", None) for s in args.seeds.split(",") if s]
    plan += [(int(s), "control", CONTROL)
             for s in args.control_seeds.split(",") if s]
    for seed, variant, override in plan:
        t0 = time.perf_counter()
        try:
            res = harness.run_cell(cell, seed, args.seconds, False, t0,
                                   engine_override=override,
                                   counter=counter)
            line = {"workload": cell.name, "seed": seed, "variant": variant,
                    "correct": res["correct"], "checks": res["checks"],
                    "attempted": res["attempted"], "failed": res["failed"],
                    "metrics": res["metrics"]}
        except Exception as e:  # noqa: BLE001 — a control may crash
            line = {"workload": cell.name, "seed": seed, "variant": variant,
                    "error": f"{type(e).__name__}: {e}"}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
