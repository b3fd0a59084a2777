"""The trace reduction, on hand-made events and on two small traces
recorded on a TPU v5e (``record_trace.py``: 256 single-key reads through
the queue over 2^20 keys, frozen and mutable with 8 writes)."""
from pathlib import Path

import pytest

from bench import tracing
from bench.tracing import Event

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, end, line=""):
    return Event(name, float(start), float(end), line)


def test_busy_union_and_idle_gaps():
    mods = [ev("jit_a(1)", 10, 20), ev("jit_b(2)", 15, 30),
            ev("jit_a(1)", 50, 60), ev("jit_c(3)", 95, 120)]
    assert tracing.busy_intervals(mods) == [(10, 30), (50, 60), (95, 120)]
    window = (0, 100)
    assert tracing.busy_ns(mods, window) == 20 + 10 + 5
    gaps = tracing.idle_gaps(mods, window)
    assert gaps == [(0, 10), (30, 50), (60, 95)]
    assert sum(b - a for a, b in gaps) + 35 == 100


def test_gaps_take_the_span_that_overlaps_most_innermost_on_ties():
    spans = [ev("bench.result", 0, 100, "t1"),
             ev("queue.result", 5, 100, "t1"),
             ev("bench.send", 40, 48, "t2"),
             ev("store.fold", 60, 90, "t3")]
    gaps = [(10, 20), (40, 50), (55, 95), (200, 210)]
    assert tracing.label_gaps(gaps, spans) == [
        ("queue.result", 10), ("queue.result", 10),
        ("queue.result", 40), ("no span", 10)]


def test_self_time_of_nested_ops():
    ops = [ev("%cond.1 = conditional", 0, 100),
           ev("%kernel.2 = custom-call", 10, 80),
           ev("%fusion.3 = fusion", 85, 95),
           ev("%sort.4 = sort", 100, 110)]
    got = {op.name.split()[0]: ns for op, ns in tracing.op_self_ns(ops)}
    assert got == {"%cond.1": 20, "%kernel.2": 70, "%fusion.3": 10,
                   "%sort.4": 10}


def test_launches_pair_with_executions_to_find_the_clock_offset():
    mods = [ev("jit_f(7)", 1000 + t, 1010 + t) for t in (0, 100, 200)]
    launches = [ev("f", t - 0, t + 5, "main") for t in (0, 100, 190)]
    assert tracing.clock_offset(mods, launches) == 1000


def test_launch_categories_use_the_innermost_harness_span():
    tr = tracing.Trace(
        modules=[], ops=[], window=(0, 100),
        spans=[ev("bench.result", 0, 50, "t1"), ev("bench.probe", 10, 20,
                                                   "t1"),
               ev("bench.send", 60, 70, "t2")],
        launches=[ev("pipeline", 12, 13, "t1"), ev("slice", 30, 31, "t1"),
                  ev("pipeline", 61, 62, "t2"), ev("copy", 80, 81, "t2")])
    assert tracing.launch_categories(tr) == {
        "pipeline": {"bench.probe": 1, "bench.send": 1},
        "slice": {"bench.result": 1}, "copy": {"none": 1}}


@pytest.mark.parametrize("name,lookup_fn",
                         [("frozen", "pipeline"), ("mutable", "fused")])
def test_recorded_trace(name, lookup_fn):
    path = DATA / f"{name}.xplane.pb"
    tr = tracing.load(str(path))
    lo, hi = tr.window
    assert hi - lo > 0
    # the device clock is offset from the host's by about a millisecond
    assert tr.offset_ns != 0 and abs(tr.offset_ns) < 10e6
    busy = tracing.busy_ns(tr.modules, tr.window)
    idle = sum(b - a for a, b in tracing.idle_gaps(tr.modules, tr.window))
    assert busy > 0 and abs(busy + idle - (hi - lo)) < 1.0
    cats = tracing.launch_categories(tr)
    assert set(cats[lookup_fn]) == {"bench.probe"}
    assert set(cats["dynamic_slice"]) == {"bench.result"}
    by = tracing.device_ns_by_category(tr)
    assert by["bench.probe"] > 0 and by["bench.result"] > 0
    red = tracing.reduce(str(path))
    assert red.span_counts["bench.probe"] >= 4
    assert 0 < red.busy_s < red.window_s
    kernels = red.kernel_s
    assert any(k.endswith(":top_descent") for k in kernels)
    assert any(not k.endswith(":top_descent") for k in kernels)
    bd = red.breakdown
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(bd[key]) <= 10
        assert all(s > 0 for _, s in bd[key])
    assert sum(s for _, s in bd["idle_gaps"]) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
