"""The reduction from a profiler trace to busy time, idle gaps, program
time and the breakdown, on a small synthesized trace."""
import pytest

from bench import trace as btrace
from bench.trace import Event

HOST = ("/host:CPU", "python")
TPU = "/device:TPU:0"


def _trace_events():
    return [
        Event(*HOST, btrace.WINDOW, 0.0, 1000.0),
        Event(*HOST, "bench.tick", 90.0, 450.0),
        Event(*HOST, "bench.wait", 450.0, 590.0),
        Event(*HOST, "bench.submit", 590.0, 600.0),
        Event(TPU, "XLA Ops", "fusion.1", 100.0, 300.0),
        Event(TPU, "XLA Ops", "custom-call.2", 250.0, 400.0),
        Event(TPU, "XLA Ops", "fusion.1", 600.0, 700.0),
        Event(TPU, "XLA Ops", "fusion.1", 1200.0, 1300.0),  # after the window
        Event(TPU, "XLA Modules", "jit_streaming_fused_scan(1)", 100.0, 400.0),
        Event(TPU, "XLA Modules", "jit_other", 600.0, 700.0),
        Event("/device:TPU:0 SparseCore", "XLA Ops", "other", 0.0, 1000.0),
        Event("/host:CPU", "other-thread", "ignored", 0.0, 1000.0),
    ]


def test_reduce_keeps_window_device_and_host_thread():
    tr = btrace.reduce(_trace_events())
    assert tr.window == (0.0, 1000.0)
    assert tr.window_s == pytest.approx(1e-6)
    assert list(tr.ops) == [TPU] and len(tr.ops[TPU]) == 4
    assert len(tr.modules[TPU]) == 2
    assert {e.name for e in tr.host} == {"bench.tick", "bench.wait",
                                         "bench.submit"}


def test_reduce_without_window_is_none():
    assert btrace.reduce([Event(TPU, "XLA Ops", "x", 0.0, 1.0)]) is None


def test_busy_is_the_union_inside_the_window():
    tr = btrace.reduce(_trace_events())
    assert btrace.busy_intervals(tr.ops[TPU], tr.window) == [
        (100.0, 400.0), (600.0, 700.0)]
    assert btrace.busy_s(tr) == pytest.approx(400e-9)
    assert btrace.idle_pct(tr) == pytest.approx(60.0)


def test_busy_averages_over_chips():
    evs = _trace_events() + [
        Event("/device:TPU:1", "XLA Ops", "fusion.1", 0.0, 1000.0)]
    tr = btrace.reduce(evs)
    assert btrace.busy_s(tr) == pytest.approx((400e-9 + 1000e-9) / 2)


def test_no_device_operation_reads_nothing():
    tr = btrace.reduce([Event(*HOST, btrace.WINDOW, 0.0, 10.0)])
    assert btrace.busy_s(tr) is None
    assert btrace.idle_pct(tr) is None
    assert btrace.idle_pct(None) is None


def test_module_time_by_name():
    tr = btrace.reduce(_trace_events())
    secs, n = btrace.module_s(tr, "streaming_fused_scan")
    assert (secs, n) == (pytest.approx(300e-9), 1)
    assert btrace.module_s(tr, "absent") == (0.0, 0)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    tr = btrace.reduce(_trace_events())
    gaps = btrace.idle_gaps(tr)
    assert [g[0] for g in gaps] == [btrace.WINDOW, "bench.wait", btrace.WINDOW]
    assert [g[1] for g in gaps] == pytest.approx([100e-9, 200e-9, 300e-9])


def test_breakdown_ranks_ops_and_idle():
    b = btrace.breakdown(btrace.reduce(_trace_events()), top=10)
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["device_ops"][0][1] == pytest.approx(300e-9)
    assert b["device_ops"][1] == ["custom-call.2", pytest.approx(150e-9)]
    assert b["idle_gaps"][0] == [btrace.WINDOW, pytest.approx(400e-9)]
    assert b["idle_gaps"][1] == ["bench.wait", pytest.approx(200e-9)]
    assert len(btrace.breakdown(btrace.reduce(_trace_events()), top=1)
               ["device_ops"]) == 1
