"""The reduction from a profiler trace to busy time, program time and
idle gaps, on each small trace in ``data/`` (each file says where it
came from), against sums worked out here by brute force."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import devtrace

DATA = sorted((Path(__file__).resolve().parent / "data").glob("*.json"))


@pytest.fixture(scope="module", params=DATA, ids=[p.stem for p in DATA])
def recorded(request):
    d = json.loads(request.param.read_text())
    dev = [tuple(e) for e in d["device"]]
    host = [tuple(e) for e in d["host"]]
    return tuple(d["window"]), dev, host


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _ops(dev, lo, hi):
    return [(max(s, lo), min(e, hi), name, s, e)
            for _, line, name, s, e in dev
            if line == devtrace.OPS_LINE and min(e, hi) > max(s, lo)]


def test_busy_is_union_of_ops_in_window(recorded):
    (lo, hi), dev, host = recorded
    red = devtrace.reduce(dev, host, (lo, hi))
    busy = sum(e - s for s, e in _union((s, e) for s, e, *_ in
                                        _ops(dev, lo, hi)))
    assert red.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0.0 < red.idle_share() < 1.0


def test_program_time_counts_ops_inside_each_program(recorded):
    (lo, hi), dev, host = recorded
    red = devtrace.reduce(dev, host, (lo, hi))
    mods = [(s, e, devtrace.program_name(n)) for _, line, n, s, e in dev
            if line == devtrace.MODULES_LINE]
    want = {}
    for cs, ce, _, s, e in _ops(dev, lo, hi):
        mid = 0.5 * (s + e)
        for ms, me, prog in mods:
            if ms <= mid <= me:
                want.setdefault(prog, []).append((cs, ce))
    assert want, "the trace holds ops inside programs"
    for prog, iv in want.items():
        secs = sum(e - s for s, e in _union(iv)) * 1e-9
        assert red.program_s[prog] == pytest.approx(secs, rel=1e-9)
    assert sum(red.program_s.values()) <= red.busy_s * (1 + 1e-12)


def test_idle_gaps_longest_first_and_named_by_host_span(recorded):
    (lo, hi), dev, host = recorded
    red = devtrace.reduce(dev, host, (lo, hi))
    merged = _union((s, e) for s, e, *_ in _ops(dev, lo, hi))
    holes, edge = [], lo
    for s, e in merged + [[hi, hi]]:
        if s > edge:
            holes.append((edge, s))
        edge = max(edge, e)
    holes.sort(key=lambda g: g[0] - g[1])

    def name(a, b):
        best, cover = None, 0.0
        for n, s, e in host:
            c = min(e, b) - max(s, a)
            if c > cover and n not in devtrace.CONTAINERS:
                best, cover = n, c
        if best is None:
            for n, s, e in host:
                c = min(e, b) - max(s, a)
                if c > cover:
                    best, cover = n, c
        return best or "host"

    want = [(name(a, b), (b - a) * 1e-9) for a, b in holes[:devtrace.TOP]]
    assert [g[0] for g in red.gaps] == [g[0] for g in want]
    assert [g[1] for g in red.gaps] == pytest.approx([g[1] for g in want],
                                                     rel=1e-9)
    assert sum(g[1] for g in red.gaps) <= (red.window_s - red.busy_s) * (
        1 + 1e-12)


def test_window_without_device_ops_is_an_error(recorded):
    (lo, hi), dev, host = recorded
    first = min(s for _, line, _, s, _ in dev if line == devtrace.OPS_LINE)
    with pytest.raises(ValueError, match="no device op"):
        devtrace.reduce(dev, host, (first - 2.0, first - 1.0))
    with pytest.raises(ValueError, match="no device op"):
        devtrace.reduce([], host, (lo, hi))


def test_union_and_clip_by_hand():
    dev = [(0, devtrace.MODULES_LINE, "jit_f(1)", 0.0, 130.0),
           (0, devtrace.OPS_LINE, "a", 10.0, 30.0),
           (0, devtrace.OPS_LINE, "b", 20.0, 40.0),
           (0, devtrace.OPS_LINE, "c", 90.0, 120.0)]
    host = [("bench.admit", 40.0, 90.0)]
    red = devtrace.reduce(dev, host, (0.0, 100.0))
    assert red.busy_s == pytest.approx(40e-9)        # 10-40 and 90-100
    assert red.program_s == {"jit_f": pytest.approx(40e-9)}
    assert red.gaps[0] == ("bench.admit", pytest.approx(50e-9))
    assert red.gaps[1] == ("host", pytest.approx(10e-9))
