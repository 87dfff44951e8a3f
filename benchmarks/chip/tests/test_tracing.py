"""The trace reduction, on a hand-made trace whose answers are worked out
by hand, and on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path

import pytest

import tracing

DATA = Path(__file__).resolve().parent / "data"
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MODS = "XLA Ops", "XLA Modules"

# device 0 runs ops over [0,10) and [5,20) (overlapping) and [30,40);
# device 1 over [0,40); the host spans the 20..30 gap with two events
HAND = [
    [D0, OPS, "fusion.1", 0.0, 10.0, "jit_step"],
    [D0, OPS, "fusion.2", 5.0, 15.0, "jit_step"],
    [D0, OPS, "_partials_kernel", 30.0, 10.0, "jit_partials"],
    [D0, MODS, "jit_step(12)", 0.0, 20.0, None],
    [D0, MODS, "jit_partials(3)", 30.0, 10.0, None],
    [D1, OPS, "fusion.9", 0.0, 40.0, "jit_other"],
    [HOST, "python3", "bench.decode_step", 15.0, 20.0, None],
    [HOST, "python3", "PjitFunction(step)", 18.0, 14.0, None],
]


def test_busy_is_the_union_averaged_over_devices():
    assert tracing.busy_intervals(HAND, D0) == [(0.0, 20.0), (30.0, 40.0)]
    assert tracing.busy_seconds(HAND) == pytest.approx((30 + 40) / 2 / 1e9)


def test_program_and_kernel_time():
    assert tracing.program_seconds(HAND, ("jit_step",)) == pytest.approx(20e-9)
    no_modules = [e for e in HAND if e[1] != MODS]
    # without a module line, the ops' module stat is summed
    assert tracing.program_seconds(no_modules, ("jit_step",)) == pytest.approx(25e-9)
    assert tracing.kernel_seconds(HAND, ("_partials_kernel",)) == pytest.approx(10e-9)


def test_idle_gap_is_named_by_the_innermost_spanning_host_event():
    # device 1 is the busier one, and it has no gap
    assert tracing.idle_gaps(HAND) == []
    only0 = [e for e in HAND if e[0] != D1]
    assert tracing.idle_gaps(only0) == [["PjitFunction(step)", pytest.approx(10e-9)]]


def test_top_ops():
    top = tracing.top_ops(HAND, k=2)
    assert [name for name, _ in top] == ["jit_other:fusion.9", "jit_step:fusion.2"]
    # ops without a module stat take the module whose run covers them
    bare = [e[:5] + [None] for e in HAND]
    assert tracing.top_ops(bare, k=1) == [["?:fusion.9", pytest.approx(40e-9)]]
    assert tracing.top_ops(bare)[1][0] == "jit_step:fusion.2"


def test_recorded_trace():
    path = DATA / "trace_v5e.json"
    if not path.exists():
        pytest.skip("no recorded trace")
    events = json.loads(path.read_text())
    planes = tracing.device_planes(events)
    assert planes
    span = max(e[3] + e[4] for e in events) - min(e[3] for e in events)
    busy = tracing.busy_seconds(events)
    assert 0 < busy <= span / 1e9
    ops = sum(e[4] for e in events if e[1] in tracing.OP_LINES) / len(planes) / 1e9
    assert busy <= ops + 1e-12
    assert tracing.top_ops(events)
