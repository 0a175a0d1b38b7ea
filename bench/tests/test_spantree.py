"""Naming idle gaps by the program's own spans (``bench/spantree.py``),
on hand-made spans in the layout the program writes: tick-sized phase
spans inside the harness's chunk span, the rate solve's spans inside
``sim.assign``, admission inside the events or the step phase."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import devtrace, spantree

DATA = sorted((Path(__file__).resolve().parent / "data").glob("*.json"))


def _ticks(n, t0=0.0, assign=60.0, components=30.0, solve=10.0,
           step=30.0, admit_in=None):
    """``n`` ticks of 100 ns from ``t0``: assign, then next_event (5),
    advance (5) and step; inside assign, components then problems (5)
    then the harness's solve span around fluid.solve_batch."""
    spans = []
    for k in range(n):
        t = t0 + 100.0 * k
        spans.append(("sim.assign", t, t + assign))
        spans.append(("fluid.components", t, t + components))
        spans.append(("fluid.problems", t + components,
                      t + components + 5.0))
        a = t + components + 5.0
        spans.append(("bench.solve", a, a + solve))
        spans.append(("fluid.solve_batch", a + 0.5, a + solve - 0.5))
        spans.append(("fluid.key", a + 1.0, a + solve - 1.0))
        t += assign
        spans.append(("sim.next_event", t, t + 5.0))
        spans.append(("sim.advance", t + 5.0, t + 10.0))
        spans.append(("sim.step", t + 10.0, t + 10.0 + step))
        if admit_in == "step":
            spans.append(("bench.admit", t + 11.0, t + 10.0 + step - 1.0))
            spans.append(("sim.admit", t + 12.0, t + 10.0 + step - 2.0))
    return spans


def _name(spans, lo, hi):
    host = [("bench.window", -1e9, 1e9), ("bench.chunk", -1e6, 1e6)]
    return spantree.name_gap(spantree.SpanIndex(host + spans), lo, hi)


def test_gap_of_many_ticks_takes_the_phase_covering_half():
    # assign covers 60% of each tick; no part of it covers half the gap
    assert _name(_ticks(200), 0.0, 20_000.0) == "sim.assign"


def test_deepest_name_covering_half_wins():
    # components take 55 of each tick's 100 ns: deeper than sim.assign
    spans = _ticks(200, assign=70.0, components=55.0, solve=5.0, step=20.0)
    assert _name(spans, 0.0, 20_000.0) == "fluid.components"


def test_no_phase_reaching_half_takes_the_parent():
    spans = _ticks(200, assign=40.0, components=10.0, solve=10.0,
                   step=40.0)
    assert _name(spans, 0.0, 20_000.0) == "bench.chunk"


def test_gap_inside_one_span_takes_the_innermost():
    spans = _ticks(3, solve=20.0, components=10.0)
    a = 10.0 + 5.0  # the first tick's solve span starts at 15
    assert _name(spans, a + 2.0, a + 18.0) == "fluid.key"


def test_admission_nests_in_the_phase_that_called_it():
    spans = _ticks(100, assign=40.0, components=10.0, solve=10.0,
                   step=50.0, admit_in="step")
    assert _name(spans, 0.0, 10_000.0) == "sim.step"
    # inside one step: the harness's bench.admit, then the program's span
    t = 40.0 + 10.0
    assert _name(spans, t + 13.0, t + 40.0) == "sim.admit"


def test_gap_without_program_spans_is_left_to_devtrace():
    host = [("bench.chunk", 0.0, 1e4), ("bench.solve", 100.0, 900.0)]
    assert spantree.name_gap(spantree.SpanIndex(host), 0.0, 1e3) is None


@pytest.mark.parametrize("path", DATA, ids=[p.stem for p in DATA])
def test_recorded_trace_without_program_spans_named_as_devtrace(path):
    d = json.loads(path.read_text())
    dev = [tuple(e) for e in d["device"]]
    host = [tuple(e) for e in d["host"]]
    window = tuple(d["window"])
    assert spantree.idle_gaps(dev, host, window) == \
        devtrace.reduce(dev, host, window).gaps


@pytest.mark.parametrize("path", DATA, ids=[p.stem for p in DATA])
def test_program_spans_rename_only_the_gaps_they_fill(path):
    """Adding program spans to a recorded trace renames the gaps they fill
    and leaves busy time, program time and op time as they were."""
    d = json.loads(path.read_text())
    dev = [tuple(e) for e in d["device"]]
    host = [tuple(e) for e in d["host"]]
    window = tuple(d["window"])
    before = devtrace.reduce(dev, host, window)
    lo, hi = window
    ticks = [(n, s, e) for n, s, e in
             _ticks(int((hi - lo) // 100), t0=lo) if e <= hi]
    after = spantree.idle_gaps(dev, host + ticks, window)
    assert [secs for _, secs in after] == [secs for _, secs in before.gaps]
    assert {n for n, _ in after} <= {"sim.assign", "fluid.components",
                                     "fluid.key", "sim.step",
                                     "sim.next_event", "sim.advance",
                                     "fluid.problems", "bench.solve",
                                     "fluid.solve_batch", "bench.chunk"}
    red = devtrace.reduce(dev, host + ticks, window)
    assert (red.busy_s, red.program_s, red.op_s) == (
        before.busy_s, before.program_s, before.op_s)
