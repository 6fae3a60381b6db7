"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) cell
lowers, SPMD-partitions, and compiles for the production meshes, and emit
the roofline raw data (memory analysis, FLOPs, HBM bytes, collective bytes).

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep

Results are cached as JSON under experiments/dryrun/. ``--all`` runs each
cell in a SUBPROCESS (fresh XLA state; a failing cell doesn't kill the
sweep). See EXPERIMENTS.md §Dry-run.
"""
# The 512 placeholder devices MUST be configured before any jax import.
# They are CPU devices: the dry-run never claims an accelerator, and the
# ``--all`` sweep's child processes inherit both settings.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config
from repro.models import layers as LAYERS
LAYERS.TP_AXIS = "model"     # activation sharding constraints live
# DP_AXES set per-mesh in run_cell
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.runtime import sharding as SH
from repro.runtime.analysis import (analytic_hbm_bytes, hlo_collective_bytes,
                                    jaxpr_cost, roofline_terms)

SHAPES = {
    "train_4k":    dict(kind="train",   seq=4096,    gb=256),
    "prefill_32k": dict(kind="prefill", seq=32768,   gb=32),
    "decode_32k":  dict(kind="decode",  seq=32768,   gb=128),
    "long_500k":   dict(kind="decode",  seq=524288,  gb=1, seq_shard=True,
                        subquad_only=True),
}

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
OUT_DIR = os.path.abspath(os.path.join(os.getcwd(), "experiments", "dryrun"))

DTYPE = jnp.bfloat16
TP = 16


def cell_is_applicable(arch: str, shape: str) -> tuple[bool, str]:
    cfg = get_config(arch)
    sh = SHAPES[shape]
    if sh.get("subquad_only") and not cfg.sub_quadratic:
        return False, ("SKIP: long_500k requires sub-quadratic attention; "
                       f"{arch} is pure full-attention (DESIGN.md §5)")
    return True, ""


def needs_fsdp(cfg) -> bool:
    """params(bf16) + grads(fp32) + AdamW(fp32 m,v) under TP-only sharding
    must fit ~8 GiB of the 16 GiB v5e HBM, else shard over the data axes."""
    return cfg.n_params() * (2 + 4 + 8) / TP > 8e9


def pick_microbatch(cfg, gb: int, seq: int, data_shards: int,
                    budget_bytes: float = 3e9) -> int | None:
    """Largest microbatch whose sqrt-remat residuals fit the budget."""
    import math
    nl = cfg.num_layers
    g = max(1, int(math.sqrt(nl)))
    live = g + nl // g
    full_tok = gb * seq / data_shards
    h_bytes = full_tok * cfg.d_model * 2 * live
    if h_bytes <= budget_bytes:
        return None                                  # no accumulation needed
    mb = gb
    while mb > data_shards:
        cand = mb // 2
        if gb % cand or cand < data_shards:
            break
        mb = cand
        if (mb * seq / data_shards) * cfg.d_model * 2 * live <= budget_bytes:
            return mb
    return mb


def model_flops_for(cfg, kind: str, gb: int, seq: int) -> float:
    n_active = cfg.n_active_params()
    if kind == "train":
        return 6.0 * n_active * gb * seq
    if kind == "prefill":
        return 2.0 * n_active * gb * seq
    return 2.0 * n_active * gb          # decode: one token per sequence


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    sh = SHAPES[shape]
    kind, seq, gb = sh["kind"], sh["seq"], sh["gb"]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    res: dict = {"arch": arch, "shape": shape,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": kind, "n_chips": int(n_chips)}

    SH.set_axis_sizes(mesh)
    LAYERS.DP_AXES = tuple(a for a in mesh.axis_names if a != "model")
    LAYERS.MESH = mesh
    data_shards = n_chips // TP
    fsdp_axes = tuple(a for a in mesh.axis_names if a != "model") \
        if needs_fsdp(cfg) else ()
    res["fsdp"] = bool(fsdp_axes)

    ap = M.abstract_params(cfg, tp=TP, dtype=DTYPE)
    pspecs = SH.param_specs(ap, fsdp_axes)
    p_shard = SH.shardings(mesh, pspecs)
    t0 = time.time()

    if kind == "train":
        mb = pick_microbatch(cfg, gb, seq, data_shards)
        res["microbatch"] = mb
        aopt = M.abstract_opt_state(ap)
        ospecs = SH.opt_state_specs(pspecs)
        batch = M.train_input_specs(cfg, gb, seq)
        bspec = SH.batch_spec(mesh)
        data_axes = tuple(a for a in mesh.axis_names if a != "model")
        step = M.make_train_step(cfg, tp=TP,
                                 hp=M.TrainHParams(microbatch=mb),
                                 batch_axes=data_axes)
        jstep = jax.jit(
            step,
            in_shardings=(p_shard, SH.shardings(mesh, ospecs),
                          {k: NamedSharding(mesh, bspec) for k in batch}),
            donate_argnums=(0, 1))
        args = (ap, aopt, batch)
    elif kind == "prefill":
        tokens = jax.ShapeDtypeStruct((gb, seq), jnp.int32)
        bspec = SH.batch_spec(mesh)
        step = M.make_prefill(cfg, tp=TP)
        jstep = jax.jit(step, in_shardings=(p_shard,
                                            NamedSharding(mesh, bspec)))
        args = (ap, tokens)
    else:                                            # decode
        seq_shard = bool(sh.get("seq_shard"))
        acache = M.abstract_cache(cfg, gb, seq, tp=TP, dtype=DTYPE)
        cspecs = SH.cache_specs(acache, mesh, seq_shard=seq_shard)
        tokens = jax.ShapeDtypeStruct((gb, 1), jnp.int32)
        tspec = P() if gb == 1 else SH.batch_spec(mesh)
        step = M.make_serve_step(cfg, tp=TP)
        jstep = jax.jit(step,
                        in_shardings=(p_shard, SH.shardings(mesh, cspecs),
                                      NamedSharding(mesh, tspec)),
                        donate_argnums=(1,))
        args = (ap, acache, tokens)

    jax.set_mesh(mesh)          # context mesh for with_sharding_constraint
    with mesh:
        lowered = jstep.lower(*args)
        res["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        res["compile_s"] = round(time.time() - t0, 2)

    # analytic HBM-fit breakdown (XLA-CPU memory_analysis is a conservative
    # upper bound: the CPU scheduler lacks TPU's memory-saving passes; the
    # fit claim uses this auditable model, both numbers are recorded)
    p_bytes = cfg.n_params()
    state_gb = 0.0
    if kind == "train":
        state_gb = p_bytes * (2 + 4 + 8) / (n_chips if res["fsdp"] else TP) \
            / 2**30
        mbsz = res.get("microbatch") or gb
        import math as _m
        g_ = max(1, int(_m.sqrt(cfg.num_layers)))
        live = g_ + cfg.num_layers // g_
        resid_gb = (mbsz * seq / data_shards) * cfg.d_model * 2 * live / 2**30
    else:
        state_gb = p_bytes * 2 / (n_chips if res["fsdp"] else TP) / 2**30
        resid_gb = 0.0
    res["analytic_fit"] = {
        "state_gb_per_chip": round(state_gb, 2),
        "remat_residuals_gb": round(resid_gb, 2),
        "fits_16gb": bool(state_gb + resid_gb + 2.0 < 16.0),
    }

    ma = compiled.memory_analysis()
    res["memory"] = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_per_device_gb": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
    }
    xla_ca = compiled.cost_analysis() or {}
    res["xla_cost_analysis"] = {k: float(v) for k, v in xla_ca.items()
                                if k in ("flops", "bytes accessed")}

    # scan-aware global flops/bytes (see runtime/analysis.py)
    cost = jaxpr_cost(step, *args)
    res["jaxpr_cost"] = cost

    coll = hlo_collective_bytes(compiled.as_text())
    res["collectives"] = coll

    hbm = analytic_hbm_bytes(cfg, kind, gb, seq, n_chips, TP)
    res["analytic_hbm_bytes_per_chip"] = hbm
    res["roofline"] = roofline_terms(
        cost["flops"], hbm * n_chips, coll["total_bytes_tpu"],
        n_chips, model_flops_for(cfg, kind, gb, seq))
    return res


def cell_path(arch: str, shape: str, mesh_tag: str) -> str:
    return os.path.join(OUT_DIR, f"{arch}__{shape}__{mesh_tag}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)

    if args.all:
        failures = []
        for arch in ARCH_IDS:
            for shape in SHAPES:
                ok, why = cell_is_applicable(arch, shape)
                meshes = ["single", "multi"]
                for mesh_tag in meshes:
                    path = cell_path(arch, shape, mesh_tag)
                    if os.path.exists(path) and not args.force:
                        continue
                    if not ok:
                        with open(path, "w") as f:
                            json.dump({"arch": arch, "shape": shape,
                                       "mesh": mesh_tag, "skipped": why}, f)
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", mesh_tag]
                    if args.force:
                        cmd.append("--force")
                    print(f"=== {arch} x {shape} x {mesh_tag}", flush=True)
                    r = subprocess.run(cmd, timeout=args.timeout)
                    if r.returncode != 0:
                        failures.append((arch, shape, mesh_tag))
        print("FAILURES:", failures or "none")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    ok, why = cell_is_applicable(args.arch, args.shape)
    mesh_tag = args.mesh
    path = cell_path(args.arch, args.shape, mesh_tag)
    if os.path.exists(path) and not args.force:
        print(f"cached: {path}")
        return
    if not ok:
        print(why)
        return
    try:
        res = run_cell(args.arch, args.shape, multi_pod=(mesh_tag == "multi"))
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    r = res["roofline"]
    print(json.dumps({k: res[k] for k in ("arch", "shape", "mesh",
                                          "lower_s", "compile_s")}))
    print(f"memory/device: {res['memory']['peak_per_device_gb']} GiB")
    print(f"terms: compute={r['compute_s']:.4g}s memory={r['memory_s']:.4g}s "
          f"collective={r['collective_s']:.4g}s dominant={r['dominant']} "
          f"useful={r['useful_ratio']:.3f} roofline_mfu={r['roofline_mfu']:.3f}")


if __name__ == "__main__":
    main()
