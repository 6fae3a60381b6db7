"""The reduction from a profiler trace to busy, idle, module and gap
numbers, on a hand-built trace and on one recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import devtrace

FIXTURE = Path(__file__).resolve().parent / "data" / "paper.scan.xplane.pb.gz"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile():
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            _ev("bench.window", 100, 1000),
            _ev("bench.prepare", 100, 200),      # covers gap 100-300
            _ev("bench.sync", 650, 100),         # covers gap 650-700
            _ev("other", 0, 2000),               # not a bench span
        ])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit__compute_kq(123)", 300, 100),
            _ev("jit__solve_batched_einsum(9)", 400, 250),
            _ev("jit__solve_batched_einsum(10)", 700, 300),
            _ev("jit_late(1)", 1050, 100),       # half inside the window
        ]),
        NS(name="XLA Ops", events=[
            _ev("a", 300, 100), _ev("b", 400, 200), _ev("c", 450, 200),
            _ev("d", 700, 300), _ev("e", 1050, 100)]),
    ])
    return NS(planes=[host, device, NS(name="/host:metadata", lines=[])])


def test_hand_built_trace():
    tr = devtrace.reduce_trace(_profile())
    assert tr.window_s == pytest.approx(1000e-9)
    # busy: 300-650 (350) + 700-1000 (300) + 1050-1100 (50)
    assert tr.busy_s == pytest.approx(700e-9)
    assert tr.idle_share == pytest.approx(0.3)
    assert tr.modules["jit__solve_batched_einsum"] == pytest.approx(550e-9)
    assert tr.module_calls["jit__solve_batched_einsum"] == 2
    assert tr.modules["jit_late"] == pytest.approx(50e-9)
    # gaps: 100-300 prepare, 650-700 sync, 1000-1050 no span
    assert tr.gaps == pytest.approx({"bench.prepare": 200e-9,
                                     "bench.sync": 50e-9,
                                     "host.none": 50e-9})
    b = devtrace.breakdown(tr)
    assert b["device_ops"][0][0] == "jit__solve_batched_einsum"
    assert b["idle_gaps"][0] == ["bench.prepare", pytest.approx(200e-9)]


def test_no_window_span_is_an_error():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        devtrace.reduce_trace(p)


def test_recorded_tpu_trace():
    """A 0.32 s ``paper.scan`` window traced on one TPU v5e: 8 steps of
    4 queries in 16 chunks (a step's queries split by v_r bucket), each
    chunk one K block and a gather + solve pair per doc group."""
    tr = devtrace.reduce_trace(devtrace.load_xspace(str(FIXTURE)))
    assert tr.n_chips == 1
    assert tr.window_s == pytest.approx(0.32068687)
    assert tr.busy_s == pytest.approx(0.278579738)
    assert tr.idle_share == pytest.approx(0.13130294982142543)
    assert tr.module_calls == {"jit__compute_kq": 16, "jit__gather_g": 64,
                               "jit__solve_batched_einsum": 64}
    # modules run one at a time on the chip: their sum is busy time,
    # and the short pauses between a module's own operations
    assert sum(tr.modules.values()) == pytest.approx(tr.busy_s, rel=1e-3)
    assert sum(tr.gaps.values()) == pytest.approx(tr.window_s - tr.busy_s)
    b = devtrace.breakdown(tr)
    assert b["device_ops"] == [
        ["jit__solve_batched_einsum", pytest.approx(0.16427838)],
        ["jit__gather_g", pytest.approx(0.10215322500000006)],
        ["jit__compute_kq", pytest.approx(0.012217563000000002)]]
    assert b["idle_gaps"] == [["bench.query_batch",
                               pytest.approx(0.04210713199999882)]]
