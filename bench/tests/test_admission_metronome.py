"""The Metronome admission reference on cases small enough to work out by
hand."""
from __future__ import annotations

import ast
import copy
import importlib
from pathlib import Path

import pytest

m = importlib.import_module("bench.admission.metronome")

LOW, HIGH = 0, 1
POD = {"req": (1, 1, 1), "spread": 1}


def _record(nodes, tasks, pods, *, free=None, alloc=None, latency=None,
            placed=None, admitted=True, align=None, inject=None):
    """An attempt record as ``bench/probes.py`` writes it, on a star of
    ``nodes`` with 25 Gbps NICs and four free GPUs each."""
    alloc = alloc or {n: 25.0 for n in nodes}
    jobs = {t["job"] for t in tasks} | {p["job"] for p in pods}
    return {
        "t_ms": 0.0, "job": pods[0]["job"], "nodes": list(nodes),
        "free": free or {n: (8, 8, 4) for n in nodes},
        "capacity": {n: (8, 8, 4) for n in nodes},
        "alloc_bw": alloc, "leaf_of": {n: "leaf0" for n in nodes},
        "uplink_alloc": {}, "link_alloc": dict(alloc),
        "link_capacity": dict(alloc),
        "pods": [dict(POD, **p) for p in pods],
        "tasks": [dict(t) for t in tasks],
        "submit_s": {j: 0.0 for j in jobs},
        "dependencies": [],
        "latency": latency or [[1.0] * len(nodes) for _ in nodes],
        "policy": {"scheduler": "metronome"},
        "score_params": dict(m.PARAMS),
        "control_before": None,
        "admitted": admitted,
        "placed": placed if placed is not None else [None] * len(pods),
        "control_after": (None if align is None
                          else {"align": align, "inject": inject or {}}),
    }


def _task(job, worker, *, period=100.0, duty=0.4, bw=20.0, prio=LOW):
    return {"job": job, "worker": worker, "priority": prio,
            "period_ms": period, "duty": duty, "bw_gbps": bw}


def _pod(job, *, period=100.0, duty=0.4, bw=20.0, prio=LOW):
    return {"job": job, "priority": prio, "period_ms": period, "duty": duty,
            "bw": bw}


def _score(rec, node, i=0):
    state = m.State(rec)
    return m.score(state, rec["pods"][i], node, m.PARAMS, "test")


def _plan(rec, node, i=0):
    """The worst score and the schemes of ``node``'s link with the pod of
    index ``i`` placed there."""
    state = m.State(rec)
    tasks = state.tasks + [m.pod_task(rec["pods"][i], node)]
    return m.plan(state, tasks, [node], m.PARAMS, "test")


def test_two_jobs_interleave_on_one_nic():
    """Two 20 Gbps jobs, duty 0.4 on one 100 ms circle: each sends for
    28.8 of the 72 slots, so together they overload the 25 Gbps NIC
    wherever they overlap.  a is pinned at slot 0 and covers [0, 28.8);
    b fits wherever [s, s + 28.8) leaves slots 0-28 alone, s = 29 .. 43:
    the score is 100 and the Score phase takes the middle, slot 36."""
    rec = _record(["n0"], [_task("a", "n0", prio=HIGH)], [_pod("b")])
    assert _score(rec, "n0") == 100.0
    worst, schemes = _plan(rec, "n0")
    assert worst == 100.0
    assert schemes["n0"]["jobs"] == ["a", "b"]
    assert schemes["n0"]["shifts"] == (0, 36)


def test_an_overloaded_nic_scores_its_least_excess():
    """Duty 0.6: each arc covers 43.2 slots, 86.4 together, so at least
    14.4 slot-widths overlap.  a covers [0, 43.2).  The least excess comes
    first at b from slot 29, covering [29, 72.2): slots 29-42 carry
    20 + 20 Gbps, 15 over the NIC each; slot 43 carries 0.2 x 20 + 20 = 24
    and slot 0 20 + 0.2 x 20 = 24, within it.  The excess is 14 x 15 = 210
    Gbps-slots against 25 x 72 = 1800: Eq. 18 gives 100 (1 - 210 / 1800)
    = 88.33."""
    rec = _record(["n0"], [_task("a", "n0", duty=0.6, prio=HIGH)],
                  [_pod("b", duty=0.6)])
    assert _score(rec, "n0") == pytest.approx(100.0 * (1 - 210.0 / 1800.0))
    assert _plan(rec, "n0")[1]["n0"]["shifts"] == (0, 29)


def test_a_low_priority_period_is_stretched_with_injected_idle():
    """The high-priority reference runs 100 ms; the low-priority job 92 ms.
    On a 100 ms circle its period is 8 ms longer: more than G_T (5 ms),
    within E_T (9.2 ms), so 8 ms of idle go into its compute phase; its
    40 ms of traffic then take 0.368 of the circle."""
    assert m.unify([100.0, 92.0], [HIGH, LOW], 5.0, 0.1, 72) == (
        100.0, [1, 1], [100.0, 100.0], [0.0, 8.0], [True, True])
    # a 92 ms job of high priority is never slowed: 100 ms leaves it 8 ms
    # off; the first base that merges it is 700 ms, eight 87.5 ms arcs,
    # 4.5 ms short (within G_T); the low 100 ms job merges exactly
    assert m.unify([100.0, 92.0, 100.0], [HIGH, HIGH, LOW], 5.0, 0.1,
                   72) == (700.0, [7, 8, 7], [100.0, 87.5, 100.0],
                           [0.0, 0.0, 0.0], [True, True, True])
    # within G_T a job is merged, not slowed: 97 ms on a 100 ms circle
    assert m.unify([100.0, 97.0], [HIGH, LOW], 5.0, 0.1, 72).inject == [
        0.0, 0.0]
    rec = _record(["n0"], [_task("a", "n0", prio=HIGH)],
                  [_pod("b", period=92.0, duty=0.5)])
    # 46 ms of traffic on a 100 ms circle: 33.12 slots, a's 28.8 slots
    # leave 43.2: still perfect
    assert _score(rec, "n0") == 100.0


def test_a_link_of_equal_priorities_slows_only_low_priority_jobs():
    """Two 100 ms / 92 ms jobs of one priority.  Both high: the 92 ms job
    is never slowed, and merges on the 700 ms circle as above.  Both low:
    the reference (the first) keeps 100 ms and the other, low, takes 8 ms
    of idle on the 100 ms circle."""
    high = m.unify([100.0, 92.0], [HIGH, HIGH], 5.0, 0.1, 72)
    assert (high.base, high.inject, high.ok) == (700.0, [0.0, 0.0],
                                                 [True, True])
    low = m.unify([100.0, 92.0], [LOW, LOW], 5.0, 0.1, 72)
    assert (low.base, low.inject, low.ok) == (100.0, [0.0, 8.0],
                                              [True, True])


def test_a_period_that_fits_no_circle_is_flagged_not_merged():
    """A high-priority 193 ms job beside a high-priority 100 ms reference:
    no base of 1 to 16 reference periods puts it within G_T (200 ms leaves
    it 7 ms short, and a high-priority job takes no idle), so it is
    incompatible: the link scores 0 and Score takes the free node; the
    controller has to leave it its own 193 ms, not 200 ms (idle injected)
    nor 100 ms (the first base's implied period)."""
    circ = m.unify([100.0, 193.0], [HIGH, HIGH], 5.0, 0.1, 72)
    assert circ.ok == [True, False]
    # low priority it takes 7 ms of idle on a 200 ms circle instead
    assert m.unify([100.0, 193.0], [HIGH, LOW], 5.0, 0.1, 72).inject == [
        0.0, 7.0]
    b = _pod("b", period=193.0, prio=HIGH)
    rec = _record(["n0", "n1"], [_task("a", "n0", prio=HIGH)], [b],
                  placed=["n1"])
    assert _score(rec, "n0") == 0.0 and _score(rec, "n1") == 100.0
    assert m.mismatch(rec) == 0
    rec["placed"] = ["n0"]
    assert m.mismatch(rec) == 1
    alone = _record(["n0"], [_task("a", "n0", prio=HIGH)], [b],
                    placed=["n0"], align={"a": (0.0, 100.0),
                                          "b": (50.0, 193.0)})
    assert m.mismatch(alone) == 0
    for period in (200.0, 100.0):
        wrong = copy.deepcopy(alone)
        wrong["control_after"]["align"]["b"] = (50.0, period)
        assert m.mismatch(wrong) == 1


def test_eq19_latency_decides_between_equal_scores():
    """Nothing contends, so every node scores 100.  The job's first pod is
    on a; b is 4 ms from a, c 2 ms: Eq. 19 maps c's delta to 100 and b's
    to 0, and c wins though b comes first.  A LowComm pod takes the worst
    location, b."""
    lat = [[1.0, 4.0, 2.0], [4.0, 1.0, 1.0], [2.0, 1.0, 1.0]]
    tasks = [_task("j", "a")]
    rec = _record(["a", "b", "c"], tasks, [_pod("j")], latency=lat,
                  placed=["c"])
    assert m.admission(rec, m.PARAMS) == (True, ["c"])
    assert m.mismatch(rec) == 0
    rec["placed"] = ["b"]
    assert m.mismatch(rec) == 1
    quiet = _record(["a", "b", "c"], [_task("j", "a", bw=0.0)],
                    [_pod("j", bw=0.0)], latency=lat, placed=["b"])
    assert m.admission(quiet, m.PARAMS) == (True, ["b"])
    assert m.mismatch(quiet) == 0


def test_spread_refuses_a_second_pod_on_the_only_free_node():
    """Two pods, spread 1, and only n0 has a free GPU for both: the second
    pod passes Filter nowhere, so the attempt is refused whole."""
    free = {"n0": (8, 8, 4), "n1": (8, 8, 0)}
    rec = _record(["n0", "n1"], [], [_pod("j"), _pod("j")], free=free,
                  admitted=False)
    assert m.admission(rec, m.PARAMS)[0] is False
    assert m.mismatch(rec) == 0
    both = copy.deepcopy(rec)
    both.update(admitted=True, placed=["n0", "n0"])
    assert m.mismatch(both) == 1


def test_a_lowcomm_pod_loads_no_link():
    """A pod with no bandwidth (LowComm) scores 100 everywhere and puts no
    demand on its NIC: a second 20 Gbps job beside it contends with
    nothing."""
    rec = _record(["n0"], [_task("q", "n0", bw=0.0, prio=HIGH)],
                  [_pod("b")])
    assert _score(rec, "n0") == 100.0 and _plan(rec, "n0")[1] == {}
    quiet = _record(["n0"], [_task("a", "n0", prio=HIGH)],
                    [_pod("q", duty=0.0)])
    assert _score(quiet, "n0") == 100.0


def test_controller_answers_each_circle_at_its_optimum():
    """b joins a on n0.  On the 100 ms circle a covers slots [0, 28.8) from
    offset 0; b at 50 ms covers [36, 64.8): no overlap, the optimum 100.
    One slot (1.39 ms) later still overlaps nothing.  At 10 ms b covers
    [7.2, 36) and overlaps a: slots 8-27 carry 40 Gbps, 15 over the NIC
    each; slots 7 and 28, each 0.8 covered by one of them, carry 36, 11
    over.  Eq. 18 gives 100 (1 - 322 / 1800) = 82.11 < 100.  A 92 ms period, or no
    alignment for b, departs from the circle; so does a period other than
    its own for a job that contends nowhere."""
    tasks = [_task("a", "n0", prio=HIGH)]
    rec = _record(["n0"], tasks, [_pod("b")], placed=["n0"],
                  align={"a": (0.0, 100.0), "b": (50.0, 100.0)})
    assert m.mismatch(rec) == 0
    late = copy.deepcopy(rec)
    late["control_after"]["align"]["b"] = (50.0 + 100.0 / 72, 100.0)
    assert m.mismatch(late) == 0
    state = m.State(rec)
    state.place(rec["pods"][0], "n0")
    jobs, specs, bw, caps = m.problem(state, state.tasks, ["n0"])
    circ = m.circle_of(specs, 72, 5.0, 0.1)[0]
    early = {"a": (0.0, 100.0), "b": (10.0, 100.0)}
    assert m.answered_score(bw[0], caps[0], jobs, specs, circ, early,
                            72) == pytest.approx(100 * (1 - 322 / 1800))
    overlap = copy.deepcopy(rec)
    overlap["control_after"]["align"] = early
    assert m.mismatch(overlap) == 1
    short = copy.deepcopy(rec)
    short["control_after"]["align"]["b"] = (50.0, 92.0)
    assert m.mismatch(short) == 1
    missing = copy.deepcopy(rec)
    del missing["control_after"]["align"]["b"]
    assert m.mismatch(missing) == 1
    free = _record(["n0", "n1"], tasks, [_pod("b", period=92.0)],
                   free={"n0": (8, 8, 0), "n1": (8, 8, 4)}, placed=["n1"],
                   align={"a": (0.0, 100.0)})
    assert m.mismatch(free) == 0
    free["control_after"]["align"]["b"] = (0.0, 100.0)
    assert m.mismatch(free) == 1


def test_other_constants_are_refused():
    rec = _record(["n0"], [], [_pod("j")], placed=["n0"])
    assert m.mismatch(rec) == 0
    rec["score_params"] = dict(m.PARAMS, di_pre=36)
    assert m.mismatch(rec) == 1


def test_a_problem_beyond_the_enumeration_limit_names_the_attempt():
    """Five 20 Gbps jobs with one arc each on one NIC: 72^4 combinations,
    over 2^22."""
    tasks = [_task(f"j{i}", "n0") for i in range(4)]
    rec = _record(["n0"], tasks, [_pod("j4")], placed=["n0"])
    with pytest.raises(m.TooLarge, match=r"attempt of j4 at 0.0 ms, pod 0"):
        m.mismatch(rec)


def test_the_reference_imports_nothing_of_the_program():
    src = Path(m.__file__).read_text()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
    assert names <= {"__future__", "functools", "itertools", "math",
                     "typing", "numpy"}
