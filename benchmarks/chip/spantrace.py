"""Charge a traced window's host time and the chip's idle time to the
innermost host span.

``devtrace.reduce_trace`` charges each idle gap of the chip to the one
``bench.*`` span that overlaps it most. The program under test opens
its own ``wmd.*`` spans inside those (``WmdEngine.query_batch``:
``wmd.query_batch`` around ``wmd.plan``, ``wmd.stage``,
``wmd.dispatch``, ``wmd.collect``, ``wmd.scatter``, ``wmd.return``).
This reduction reads both families over the same ``bench.window``:

- self time: each span's duration inside the window minus the part its
  child spans on the same host thread cover, summed per span name;
- idle by span: every interval of the window in which no operation ran
  on chip 0, cut at the span boundaries, each piece charged to the
  innermost ``bench.*`` or ``wmd.*`` span covering it (``host.none``
  where none does). The pieces sum to window - busy.

A trace of a program without ``wmd.*`` spans reduces to the
``bench.*`` spans alone.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from typing import NamedTuple

from devtrace import WINDOW_SPAN, _clip, _union

PREFIXES = ("bench.", "wmd.")
NO_SPAN = "host.none"


class Spans(NamedTuple):
    window_s: float
    idle_s: float                 # window - busy, chip 0
    self_s: dict                  # span name -> host self seconds
    idle: dict                    # span name -> idle seconds of chip 0


def _innermost(spans, lo, hi) -> list:
    """``[(start, end, name)]`` pieces that partition ``[lo, hi)``, each
    charged to the innermost of ``spans`` (``(name, start, end)``) that
    covers it: the latest to start, then the first to end."""
    spans = sorted(spans, key=lambda x: x[1])
    cuts = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                             if lo < t < hi)})
    pieces, active, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][1] <= a:
            name, s, e = spans[j]
            heapq.heappush(active, (-s, e, j, name))
            j += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        name = active[0][3] if active else NO_SPAN
        if pieces and pieces[-1][2] == name and pieces[-1][1] == a:
            pieces[-1] = (pieces[-1][0], b, name)
        else:
            pieces.append((a, b, name))
    return pieces


def _overlap_by_name(pieces, intervals) -> dict:
    """Length of each sorted, disjoint interval's overlap with the named
    pieces, summed per name."""
    out, i = defaultdict(float), 0
    for s, e in intervals:
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        k = i
        while k < len(pieces) and pieces[k][0] < e:
            a, b, name = pieces[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return out


def reduce_spans(profile) -> Spans:
    """Self and idle seconds per span name over the ``bench.window``."""
    threads, window = [], None
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIXES):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
            threads.append(spans)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    chips = sorted((p for p in profile.planes
                    if p.name.startswith("/device:TPU:")),
                   key=lambda p: p.name)
    if not chips:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for line in chips[0].lines if line.name == "XLA Ops"
           for e in line.events]
    idle, cur = [], lo
    for s, e in _union(_clip(ops, lo, hi)):
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        idle.append((cur, hi))

    self_s = defaultdict(float)
    for spans in threads:
        for a, b, name in _innermost(spans, lo, hi):
            if name != NO_SPAN:
                self_s[name] += (b - a) * 1e-9
    every = [sp for spans in threads for sp in spans]
    charged = _overlap_by_name(_innermost(every, lo, hi), idle)
    return Spans(window_s=(hi - lo) * 1e-9,
                 idle_s=sum(e - s for s, e in idle) * 1e-9,
                 self_s=dict(self_s),
                 idle={k: v * 1e-9 for k, v in charged.items()})


def idle_by_span(spans: Spans) -> list:
    """``[[name, idle seconds], ...]``, most first, every span name: the
    entries sum to window - busy."""
    return [[k, v] for k, v in sorted(spans.idle.items(),
                                      key=lambda kv: -kv[1])]

