"""Self time and idle time charged to the innermost host span, on
hand-built traces and on one recorded on a TPU v5e with the program's
own ``wmd.*`` spans."""
from collections import Counter
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import devtrace
import spantrace

FIXTURE = (Path(__file__).resolve().parent / "data"
           / "paper.scan.spans.xplane.pb.gz")


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _profile(host_events, ops):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(n, s, e) for n, s, e in host_events])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[]),
        NS(name="XLA Ops", events=[_ev("op", s, e) for s, e in ops])])
    return NS(planes=[host, device])


# two closed-loop steps in a 1,000 ns window; the chip runs 110-310
# and 530-930, so it idles 0-110, 310-530 and 930-1000
NESTED = [
    ("bench.window", 0, 1000),
    ("other", 0, 2000),                       # neither bench.* nor wmd.*
    ("bench.prepare", 0, 50),
    ("bench.query_batch", 50, 400),
    ("wmd.query_batch", 50, 400),
    ("wmd.plan", 50, 70),
    ("wmd.stage", 70, 100),
    ("wmd.dispatch", 100, 120),
    ("wmd.dispatch", 120, 140),
    ("wmd.collect", 140, 300),
    ("wmd.scatter", 300, 400),
    ("bench.sync", 400, 450),
    ("bench.prepare", 450, 500),
    ("bench.query_batch", 500, 980),
    ("wmd.query_batch", 500, 970),
    ("wmd.plan", 500, 540),
    ("wmd.stage", 540, 560),
    ("wmd.dispatch", 560, 600),
    ("wmd.collect", 600, 900),
    ("wmd.scatter", 900, 960),
]
OPS = [(110, 200), (190, 310), (530, 930)]


def test_idle_is_cut_at_span_boundaries():
    p = _profile(NESTED, OPS)
    sp = spantrace.reduce_spans(p)
    assert sp.window_s == pytest.approx(1000e-9)
    assert sp.idle_s == pytest.approx(400e-9)
    tr = devtrace.reduce_trace(p)
    assert sp.idle_s == pytest.approx(tr.window_s - tr.busy_s)
    # the gap 310-530 straddles wmd.scatter -> bench.sync ->
    # bench.prepare -> wmd.plan: 90 + 50 + 50 + 30 ns
    assert sp.idle == pytest.approx({
        "bench.prepare": 100e-9, "wmd.plan": 50e-9, "wmd.stage": 30e-9,
        "wmd.dispatch": 10e-9, "wmd.scatter": 120e-9,
        "bench.sync": 50e-9, "wmd.query_batch": 10e-9,
        "bench.query_batch": 10e-9, "host.none": 20e-9})
    assert sum(sp.idle.values()) == pytest.approx(sp.idle_s, rel=1e-9)
    ranked = spantrace.idle_by_span(sp)
    assert ranked[0] == ["wmd.scatter", pytest.approx(120e-9)]
    assert len(ranked) == len(sp.idle)


def test_self_time_excludes_children():
    sp = spantrace.reduce_spans(_profile(NESTED, OPS))
    # wmd.query_batch lasts 350 + 470 ns, of which its children leave
    # 10 ns; bench.query_batch holds it, and keeps 970-980 to itself
    assert sp.self_s == pytest.approx({
        "bench.prepare": 100e-9, "bench.query_batch": 10e-9,
        "wmd.query_batch": 10e-9, "wmd.plan": 60e-9, "wmd.stage": 50e-9,
        "wmd.dispatch": 80e-9, "wmd.collect": 460e-9,
        "wmd.scatter": 160e-9, "bench.sync": 50e-9})
    assert sum(sp.self_s.values()) == pytest.approx(980e-9)


def test_program_without_spans_reduces_to_bench_spans():
    """A program that opens no ``wmd.*`` span: the idle time goes to the
    benchmark's spans alone, as ``devtrace`` charges it here."""
    events = [(n, s, e) for n, s, e in NESTED if not n.startswith("wmd.")]
    p = _profile(events, OPS)
    sp = spantrace.reduce_spans(p)
    assert not [k for k in sp.self_s if k.startswith("wmd.")]
    assert sp.idle == pytest.approx({
        "bench.prepare": 100e-9, "bench.query_batch": 230e-9,
        "bench.sync": 50e-9, "host.none": 20e-9})
    assert sum(sp.idle.values()) == pytest.approx(sp.idle_s)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        spantrace.reduce_spans(_profile(NESTED[1:], OPS))


def test_recorded_tpu_trace_with_program_spans():
    """A 0.28 s ``paper.scan`` window traced on one TPU v5e with the
    engine's spans: 8 steps of 4 queries, each step 2 chunks against 4
    doc groups. The spans match the engine's plan one for one, and the
    idle time they are charged sums to window - busy."""
    profile = devtrace.load_xspace(str(FIXTURE))
    tr = devtrace.reduce_trace(profile)
    sp = spantrace.reduce_spans(profile)
    assert tr.module_calls == {"jit__compute_kq": 16, "jit__gather_g": 64,
                               "jit__solve_batched_einsum": 64}
    host = [e.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    assert Counter(n for n in host if n.startswith("wmd.")) == {
        "wmd.query_batch": 8, "wmd.plan": 8, "wmd.stage": 16,
        "wmd.dispatch": 16 + 64, "wmd.collect": 64, "wmd.scatter": 64,
        "wmd.return": 8}
    assert sp.window_s == pytest.approx(tr.window_s)
    assert sp.idle_s == pytest.approx(tr.window_s - tr.busy_s, rel=1e-9)
    assert sum(sp.idle.values()) == pytest.approx(sp.idle_s, rel=1e-6)
    assert sum(v for k, v in sp.idle.items()
               if k.startswith("wmd.")) > 0.5 * sp.idle_s
    assert sp.idle["host.none"] < 0.01 * sp.idle_s
    assert set(sp.self_s) == {
        "bench.prepare", "bench.query_batch", "bench.sync",
        "wmd.query_batch", "wmd.plan", "wmd.stage", "wmd.dispatch",
        "wmd.collect", "wmd.scatter", "wmd.return"}
    # the host waits in wmd.collect for most of the window; every span's
    # self time together is the window, less what no span covers
    assert max(sp.self_s, key=sp.self_s.get) == "wmd.collect"
    assert sum(sp.self_s.values()) <= sp.window_s
    # the old reduction still charges every gap to bench.query_batch
    assert list(tr.gaps) == ["bench.query_batch"]
