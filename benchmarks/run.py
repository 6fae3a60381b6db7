"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (paper mapping in each module doc).
``--json PATH`` additionally writes a ``{bench_name: usec}`` record file
(e.g. ``--json BENCH_fig6.json``) for the bench trajectory; ``--only`` runs
a subset of modules.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from . import (fig5_scaling, fig6_multi_query, fig7_cdist, fig8_topk_prune,
               fig9_ivf_prune, fig10_solve_adaptive, fig11_sharded,
               fig12_serving, fig13_pareto, fig14_shard_chaos, fig15_kcache,
               moe_router, python_baseline, table1_profile)

MODULES = [
    ("table1_profile", table1_profile),
    ("python_baseline", python_baseline),
    ("fig5_scaling", fig5_scaling),
    ("fig6_multi_query", fig6_multi_query),
    ("fig7_cdist", fig7_cdist),
    ("fig8_topk_prune", fig8_topk_prune),
    ("fig9_ivf_prune", fig9_ivf_prune),
    ("fig10_solve_adaptive", fig10_solve_adaptive),
    ("fig11_sharded", fig11_sharded),
    ("fig12_serving", fig12_serving),
    ("fig13_pareto", fig13_pareto),
    ("fig14_shard_chaos", fig14_shard_chaos),
    ("fig15_kcache", fig15_kcache),
    ("moe_router", moe_router),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write {bench: usec} JSON records to PATH")
    ap.add_argument("--only", nargs="+", default=None,
                    choices=[name for name, _ in MODULES],
                    help="run only these modules")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    records: dict[str, float] = {}

    def out(line: str) -> None:
        print(line)
        parts = str(line).split(",")
        if len(parts) >= 2:
            try:
                records[parts[0]] = float(parts[1])
            except ValueError:
                pass

    print("name,us_per_call,derived")
    failures = []
    for name, mod in MODULES:
        if args.only is not None and name not in args.only:
            continue
        try:
            mod.main(out=out)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=2, sort_keys=True)
        print(f"wrote {len(records)} records to {args.json}", file=sys.stderr)
    if failures:
        print(f"FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
