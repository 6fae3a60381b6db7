"""Run one benchmark cell once: set-up, window, check, metrics.

Everything that belongs to one cell is found by name:

- ``BENCHMARK.json`` (the repository root): the cell's configuration,
  traffic and chips, and the metrics it reports;
- ``configs/<config>.json``: the deployment's sizes, data generator and
  engine settings;
- ``traffic/<traffic>.json``: the traffic's parameters, and in
  ``runner`` the name of the module that reads them;
- ``runners/<runner>.py``: the load loop, which builds the program
  under test, warms it, drives the window and checks what it produced
  (see ``Ctx``);
- ``limits/<workload>.json``: the limits of the comparison with the
  plain reference;
- ``metrics/<metric>.py``: ``read(run) -> float | None`` for each metric.

A new cell, traffic, runner or metric is a new file beside these; no
file here names one.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path
from typing import NamedTuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

import checks  # noqa: E402
import data  # noqa: E402


TRACE_SECONDS = 5.0


class Cell(NamedTuple):
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    metrics: dict       # trace flag -> [(name, unit)]


class Ctx(NamedTuple):
    """What a runner gets. A runner module has two functions:

    - ``run(ctx) -> dict``: build the program over ``ctx.corpus`` (with
      ``ctx.engine_override`` applied: the calibration's control), warm
      every shape its traffic uses, then measure for ``ctx.seconds``
      inside ``profiled(ctx.trace_dir)``. The dict holds ``kind``,
      ``t_window`` (``perf_counter`` at the window's start),
      ``compiles`` (inside the window, from ``ctx.counter``), and
      whatever its metric readers and ``check`` read. The program's
      state is dropped when ``run`` returns.
    - ``check(ctx, run) -> (readings, attempted, failed)``: compare what
      the window produced with the plain reference; ``readings`` are
      judged against the cell's limits (``checks.judge``).
    """

    cell: Cell
    corpus: data.Corpus
    seed: int
    seconds: float
    counter: "CompileCounter"
    trace_dir: str | None
    engine_override: dict
    log: object


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(ROOT / conf["file"])
    traffic = _load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(HERE / "limits" / f"{workload}.json")

    def reported(defs):
        return [(m["name"], m["unit"]) for m in defs
                if workload in m.get("workloads", [workload])]
    return Cell(workload, config, traffic, limits, int(w["chips"]),
                {0: reported(bench["end_to_end"]),
                 1: reported(bench["per_layer"])})


def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module("metrics", name).read


def runner(name: str):
    return _module("runners", name)


def peaks(kind: str) -> dict:
    table = _load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    the fixed ``<checkout>/.jax_cache``; every program is kept, however
    fast it compiled, so a second run finds all of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compiles as they happen; a load from the
    persistent cache is timed as the same event, so it counts too."""

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def new_engine(ctx: Ctx):
    """The single-chip program under test over the seeded corpus: the
    index built once, and a ``WmdEngine`` over it with the
    configuration's settings and the context's override."""
    from repro.core import WmdEngine, build_index
    from repro.core.sparse import PaddedDocs
    docs = ctx.corpus.docs
    index = build_index(PaddedDocs(idx=docs.ids, val=docs.val),
                        ctx.corpus.vecs)
    kw = dict(ctx.cell.config["engine"])
    kw.update(ctx.engine_override)
    return WmdEngine(index, **kw)


@contextlib.contextmanager
def profiled(trace_dir):
    """Profile the window into ``trace_dir`` (nothing when ``None``)."""
    if trace_dir is None:
        yield
        return
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, engine_override: dict | None = None,
             counter: CompileCounter | None = None,
             log=sys.stderr) -> dict:
    """One run of one cell; returns the result line's object.
    ``engine_override`` changes engine settings (the calibration's
    control); ``counter`` is the process's compile counter. A traced
    run measures at most ``TRACE_SECONDS``: the profiler's trace grows
    by some 20 MB a second of a busy window."""
    import devtrace

    drv = runner(cell.traffic["runner"])
    trace_dir = tempfile.mkdtemp(prefix="wmd-trace-") if trace else None
    ctx = Ctx(cell, data.make_corpus(cell.config, seed), seed,
              min(seconds, TRACE_SECONDS) if trace else seconds,
              counter or CompileCounter(), trace_dir,
              dict(engine_override or {}), log)
    run = drv.run(ctx)
    run["setup_s"] = run["t_window"] - t_start
    print(f"window compiles: {run['compiles']}", file=log, flush=True)
    info = device_info()
    info["memory_peak_bytes"] = memory_peak()
    if trace:
        tr = devtrace.reduce_trace(devtrace.load_xspace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = tr
        info["busy_s"] = tr.busy_s
        info["window_s"] = tr.window_s
    run["config"] = cell.config
    run["peaks"] = (peaks(info["kind"]) if info["platform"] == "tpu"
                    else None)

    gc.collect()    # the program's state goes before the reference runs
    readings, attempted, failed = drv.check(ctx, run)
    correct, compared = checks.judge(readings, cell.limits)

    metrics = {}
    for name, unit in cell.metrics[int(bool(trace))]:
        value = metric_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    out = {"correct": correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": info}
    if trace:
        out["breakdown"] = devtrace.breakdown(run["trace"])
    out["checks"] = compared
    return out
