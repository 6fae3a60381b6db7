"""Reduce a profiler trace to the numbers the benchmark reports.

``jax.profiler.trace`` writes an XSpace (``*.xplane.pb``). On a TPU it
holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Modules``
line has one event per executable run (named ``jit_<fn>(<hash>)``) and
whose ``XLA Ops`` line has one event per operation, and a ``/host:CPU``
plane with a line per host thread. Host and device events share one
clock. The benchmark puts its own ``TraceAnnotation`` spans, named
``bench.<what>``, around each call into a layer; ``bench.window`` marks
the measured window.

From these:

- busy time: the union of the operation intervals of each chip inside
  the window, averaged over the chips; idle share = 1 - busy / window;
- module times: device seconds per module name, hash stripped;
- idle gaps: each interval of the window in which no operation ran on
  a chip, charged to the ``bench.*`` host span that covers most of it
  (``host.none`` where no span does).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict
from typing import NamedTuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


class Trace(NamedTuple):
    window_s: float
    busy_s: float                 # mean over chips
    modules: dict                 # module name -> device seconds (all chips)
    module_calls: dict            # module name -> number of runs
    gaps: dict                    # host span name -> idle seconds (chip 0)
    n_chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load_xspace(path: str):
    """ProfileData from an ``.xplane.pb`` file, gzipped or not, or from
    the newest one under a ``jax.profiler.trace`` directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(event_name: str) -> str:
    """``jit__solve_batched_einsum(1234)`` -> ``jit__solve_batched_einsum``."""
    return event_name.split("(", 1)[0]


def reduce_trace(profile) -> Trace:
    """Busy, idle, module and gap numbers over the ``bench.window`` span."""
    spans = []
    window = None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = iv
                else:
                    spans.append((ev.name, iv))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    busy, modules, calls, gaps = [], defaultdict(float), defaultdict(int), {}
    chips = sorted((p for p in profile.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    for ci, plane in enumerate(chips):
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif line.name == "XLA Modules":
                for e in line.events:
                    s, t = e.start_ns, e.start_ns + e.duration_ns
                    if t > lo and s < hi:
                        name = module_name(e.name)
                        modules[name] += (min(t, hi) - max(s, lo)) * 1e-9
                        calls[name] += 1
        merged = _union(_clip(ops, lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if ci == 0:
            gaps = _attribute_gaps(merged, lo, hi, spans)
    if not chips:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    return Trace(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(busy),
                 modules=dict(modules), module_calls=dict(calls),
                 gaps=gaps, n_chips=len(chips))


def _attribute_gaps(merged, lo, hi, spans) -> dict:
    """Idle seconds per host span name: each gap goes to the span that
    overlaps it most."""
    idle, cur = [], lo
    for s, e in merged:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        idle.append((cur, hi))
    by_start = sorted(spans, key=lambda x: x[1][0])
    starts = [iv[0] for _, iv in by_start]
    out = defaultdict(float)
    longest = max((e - s for _, (s, e) in by_start), default=0)
    for gs, ge in idle:
        best, best_ov = "host.none", 0
        # spans starting before ge and ending after gs
        j = bisect.bisect_left(starts, ge)
        i = bisect.bisect_left(starts, gs - longest)
        for name, (s, e) in by_start[i:j]:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        out[best] += (ge - gs) * 1e-9
    return dict(out)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device modules that took
    most time and the idle seconds by host span, each at most ``top``."""
    ops = sorted(trace.modules.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace.gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
