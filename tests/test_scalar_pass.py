"""The event loop's scalar pass (DESIGN.md section 17).

Active sets of at most ``simulator._SCALAR_MAX_FLOWS`` flows take
next-event and advance as one scalar pass over the membership cache's
lists; larger ones take numpy's vector calls.  Both run the same float
operations in the same order, so:

  * every pinned run reproduces ``tests/goldens/sim_goldens.json`` byte
    for byte with the cutoff at 0 (vector calls only), at 2 (both paths
    in one run) and at its default;
  * one tick on a hand-built flow table leaves the same state on both
    paths;
  * ``SimProfile.scalar_ticks`` counts the ticks the scalar pass took.
"""
import math

import numpy as np
import pytest

from goldens import record
from repro.core import simulator
from repro.core.simulator import ClusterSimulator, SimConfig
from test_event_loop import (_pair_jobs, _scheduled, assert_golden,
                             small_cluster)

CUTOFFS = {"vectorized": 0, "mixed": 2,
           "default": simulator._SCALAR_MAX_FLOWS}


@pytest.fixture(params=sorted(CUTOFFS), ids=sorted(CUTOFFS))
def cutoff(request, monkeypatch):
    """The cutoff's name; the module constant set to its value."""
    monkeypatch.setattr(simulator, "_SCALAR_MAX_FLOWS",
                        CUTOFFS[request.param])
    return request.param


@pytest.mark.parametrize("name", sorted(record.runs()))
def test_goldens_under_cutoff(cutoff, name):
    """Every pinned run (leaf-spanning multi-link jobs in F2/F4/J1,
    several flows on one link throughout) is byte for byte the fixture's
    whichever path its ticks take."""
    assert_golden(name, record.runs()[name]())


def _profiled_run():
    jobs = _pair_jobs()
    cl, registry = _scheduled(jobs)
    sim = ClusterSimulator(
        cl, jobs, SimConfig(duration_ms=4_000.0, seed=0, jitter_std=0.0,
                            profile=True), registry=registry)
    return sim.run().profile


def test_scalar_ticks_counted(cutoff):
    p = _profiled_run()
    assert 0 < p.flow_ticks <= p.ticks
    if cutoff == "vectorized":
        assert p.scalar_ticks == 0
    elif cutoff == "default":
        assert p.scalar_ticks == p.flow_ticks
    else:  # up to two flows scalar, the four-flow ticks vectorized
        assert 0 < p.scalar_ticks < p.flow_ticks


def _hand_built():
    """Five flows of three jobs on n0, n1 and the two-link path (n1, n0):
    b is starved (rate 0); a and c share n0 and both finish exactly at
    50 ms; d crosses both links; e finishes only when advanced past its
    own end, its move clamped to what it has left."""
    sim = ClusterSimulator(small_cluster(), [],
                           SimConfig(duration_ms=1_000.0))
    tbl = sim._flows
    a, b = tbl.reserve(0, [10.0, 4.0], [("n0",), ("n1",)]).tolist()
    c, d = tbl.reserve(1, [5.0, 2.0], [("n0",), ("n1", "n0")]).tolist()
    (e,) = tbl.reserve(2, [3.0], [("n1",)]).tolist()
    flows = {a: (10.0, 0.5), b: (0.0, 1.0), c: (5.0, 0.25),
             d: (2.0, 1.0), e: (3.0, 0.2)}
    for s, (rate, rem) in flows.items():
        tbl.alive[s] = True
        tbl.rate[s] = rate
        tbl.remaining[s] = rem
        sim._active_bits |= 1 << s
    sim._junfin[:3] = [2, 2, 1]
    # sums that round differently when added in another order
    sim._delivered_vec[:] = [0.3, 0.7]
    return sim, (a, b, c, d, e)


def _state(sim):
    return (sim._flows.remaining.tobytes(), sim._delivered_vec.tobytes(),
            sim._junfin.tolist(), sim._active_bits,
            sorted(sim._dirty_links))


def _ticks(sim, advance_past=70.0):
    """Two ticks, to the first finish and then ``advance_past`` ms on:
    the active set, the next event and the state after each, and whether
    each took the scalar pass."""
    out, scalar = [], []
    for dt in (None, advance_past):
        act = sim._active_slots()
        nxt, gathered = sim._next_flow_finish(act, math.inf)
        scalar.append(gathered is not None)
        if dt is None:
            dt = nxt - sim.now
        sim._advance_flows(act, dt, gathered)
        sim.now += dt
        out.append((act.tolist(), nxt, _state(sim)))
        sim._dirty_links.clear()
    return out, scalar


def test_hand_built_tick_same_on_both_paths(monkeypatch):
    monkeypatch.setattr(simulator, "_SCALAR_MAX_FLOWS", 0)
    vec_sim, (a, b, c, d, e) = _hand_built()
    vec, vec_scalar = _ticks(vec_sim)
    monkeypatch.setattr(simulator, "_SCALAR_MAX_FLOWS", 8)
    sca_sim, _ = _hand_built()
    sca, sca_scalar = _ticks(sca_sim)
    assert sca == vec
    assert vec_scalar == [False, False] and sca_scalar == [True, True]
    # first tick: a and c finish together at 50 ms on the shared n0
    (act1, nxt1, state1), (act2, _, state2) = sca
    assert act1 == [a, b, c, d, e] and nxt1 == 50.0
    rem1 = np.frombuffer(state1[0])
    assert rem1[a] == rem1[c] == 0.0 and rem1[b] == 1.0
    assert state1[2][:3] == [1, 1, 1]
    assert state1[3] == (1 << b) | (1 << d) | (1 << e)
    assert state1[4] == ["n0"]
    # second tick: e's 70-ms move is clamped to the 0.05 it had left
    assert act2 == [b, d, e]
    rem2 = np.frombuffer(state2[0])
    assert rem2[e] == 0.0 and rem2[b] == 1.0
    assert state2[2][:3] == [1, 1, 0]
    assert state2[3] == (1 << b) | (1 << d)
    assert state2[4] == ["n1"]
    # delivered GB: every move added once per link its path crosses
    dv = np.frombuffer(state2[1])
    assert dv[0] == pytest.approx(0.3 + 0.5 + 0.25 + 0.1 + 0.14)
    assert dv[1] == pytest.approx(0.7 + 0.1 + 0.15 + 0.14 + 0.05)
