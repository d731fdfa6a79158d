"""The device-idle time inside the program's ``mint.dispatch``
annotations (``dispatch_gap_ms`` and its saturated twin), on small
synthesized traces; a trace of a program without the annotations reads
nothing."""
from types import SimpleNamespace

import pytest

from bench import program_spans, spec
from bench import trace as btrace
from bench.trace import Event

HOST = ("/host:CPU", "python")
TPU = "/device:TPU:0"
READERS = ["dispatch_gap_ms", "dispatch_gap_ms_sat"]


def _events(annotated: bool = True):
    evs = [
        Event(*HOST, btrace.WINDOW, 0.0, 1000.0),
        Event(*HOST, "bench.tick", 90.0, 950.0),
        Event(TPU, "XLA Ops", "scan.1", 120.0, 200.0),
        Event(TPU, "XLA Ops", "scan.1", 250.0, 380.0),
        Event(TPU, "XLA Ops", "fusion.2", 300.0, 390.0),  # overlaps scan.1
        Event(TPU, "XLA Ops", "scan.1", 500.0, 850.0),
        Event("/device:TPU:1", "XLA Ops", "scan.1", 0.0, 1000.0),
        Event("/host:CPU", "other-thread", program_spans.DISPATCH, 0.0, 999.0),
    ]
    if annotated:
        evs += [
            Event(*HOST, program_spans.DISPATCH, -50.0, 60.0),  # before
            Event(*HOST, program_spans.DISPATCH, 100.0, 400.0),  # idle 20+50+10
            Event(*HOST, "mint.plan_group", 110.0, 390.0),
            Event(*HOST, "mint.fetch", 130.0, 380.0),
            Event(*HOST, program_spans.DISPATCH, 500.0, 900.0),  # idle 50
        ]
    return evs


def test_idle_inside_each_dispatch_of_the_window():
    tr = btrace.reduce(_events())
    assert program_spans.idle_inside_ns(tr, program_spans.DISPATCH) == \
        pytest.approx([80.0, 50.0])
    assert program_spans.idle_inside_ns(tr, "mint.fetch") == \
        pytest.approx([50.0])


@pytest.mark.parametrize("name", READERS)
def test_reader_takes_the_median_over_dispatches(name):
    run = SimpleNamespace(trace=btrace.reduce(_events()))
    assert spec.reader(name)(run) == pytest.approx(65e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_the_annotations(name):
    """The parent program's trace, a run without a trace, and a trace
    with no device operation (a CPU run) read None."""
    read = spec.reader(name)
    assert read(SimpleNamespace(trace=btrace.reduce(_events(False)))) is None
    assert read(SimpleNamespace(trace=None)) is None
    host_only = [e for e in _events() if not e.plane.startswith("/device")]
    assert read(SimpleNamespace(trace=btrace.reduce(host_only))) is None


def test_new_readers_are_listed_in_their_cells():
    b = spec.load()
    for cell, name in [("naive-1m-exact.steady", "dispatch_gap_ms"),
                       ("naive-1m-exact.saturated", "dispatch_gap_ms_sat")]:
        listed = {m["name"] for m in spec.cell(b, cell).per_layer}
        assert name in listed and not (set(READERS) - {name}) & listed
