"""The plain references on cases small enough to work out by hand."""
from __future__ import annotations

import pytest

from bench import reference

LAYOUT = {"nodes": [{"name": "w0", "bw_gbps": 25.0},
                    {"name": "w1", "bw_gbps": 25.0},
                    {"name": "w2", "bw_gbps": 10.0}]}
JOB = {"compute_ms": 10.0, "comm_ms": 10.0, "bw_gbps": 20.0}


def test_fill_shares_a_link_and_caps_at_demand():
    rates = reference.fill([20.0, 20.0, 4.0], [("a",), ("a", "b"), ("b",)],
                           {"a": 25.0, "b": 10.0})
    # all grow to 4 (the third's demand); then b's last 2 Gbps go to the
    # second flow (6) while the first grows too; the first takes a's rest
    assert rates == pytest.approx([19.0, 6.0, 4.0])


def test_two_jobs_sharing_nics_take_half_each():
    """Both compute 10 ms, then move 0.2 Gb per NIC at 12.5 Gbps each
    (two 20 Gbps flows on a 25 Gbps NIC): 16 ms; iterations end every
    26 ms."""
    start = {"t_ms": 0.0, "jobs": {}}
    adm = [(0.0, "a", ["w0", "w1"]), (0.0, "b", ["w0", "w1"])]
    out = reference.follow({"a": JOB, "b": JOB}, start, adm,
                           {"a": 1e9, "b": 1e9}, LAYOUT, 80.0)
    assert out["a"] == pytest.approx([26.0, 52.0, 78.0])
    assert out["b"] == pytest.approx([26.0, 52.0, 78.0])


def test_follow_from_a_state_and_a_departure():
    """b is half way through its transfer when followed; a leaves at
    15 ms, so b's NIC shares end there."""
    start = {"t_ms": 5.0, "jobs": {
        "a": {"workers": ["w0", "w1"], "phase": "comm", "end": None,
              "left": {"w0": 0.2, "w1": 0.2}},
        "b": {"workers": ["w0", "w2"], "phase": "comm", "end": None,
              "left": {"w0": 0.1, "w2": 0.1}}}}
    out = reference.follow({"a": JOB, "b": JOB}, start, [],
                           {"a": 15.0, "b": 1e9}, LAYOUT, 40.0)
    # w0: 12.5 each; w2 caps b at 10: b's w2 flow ends at 5 + 10 = 15 ms,
    # its w0 flow has 0.1 - 0.125 < 0 left by then: ends at 5 + 8 = 13
    assert out["b"][0] == pytest.approx(15.0)
    assert out["a"] == []
    # b then computes until 25 and moves 0.2 Gb over w2 at 10 Gbps: 45
    assert out["b"][1:] == []


def test_progress_gap_counts_a_missing_iteration_to_the_end():
    gap = reference.progress_gap({"a": [10.0, 20.0]}, {"a": [10.5]}, 30.0)
    assert gap == pytest.approx(10.0)


# ------------------------------------------------- the controller's follower
LOW = dict(JOB, high=False)
HIGH = dict(JOB, high=True)
W01 = ["w0", "w1"]      # two 20 Gbps flows on 25 Gbps NICs: comm takes 10 ms


def _answers(align=None, inject=None):
    return {"align": align or {}, "inject": inject or {}}


@pytest.mark.parametrize("answers", [None, _answers()])
def test_no_controller_or_no_alignment_is_the_plain_follower(answers):
    """With no controller, or one that aligns no job, the follower gives
    the completions of the cases above."""
    start = {"t_ms": 0.0, "jobs": {}, "control": answers}
    adm = [(0.0, "a", W01, answers), (0.0, "b", W01, answers)]
    out = reference.follow({"a": JOB, "b": JOB}, start, adm,
                           {"a": 1e9, "b": 1e9}, LAYOUT, 80.0,
                           control=[(0.0, answers)] if answers else [])
    assert out["a"] == pytest.approx([26.0, 52.0, 78.0])
    start = {"t_ms": 5.0, "control": answers, "jobs": {
        "a": {"workers": W01, "phase": "comm", "end": None,
              "left": {"w0": 0.2, "w1": 0.2}},
        "b": {"workers": ["w0", "w2"], "phase": "comm", "end": None,
              "left": {"w0": 0.1, "w2": 0.1}, "pending": False,
              "pause": 0.0}}}
    out = reference.follow({"a": JOB, "b": JOB}, start, [],
                           {"a": 15.0, "b": 1e9}, LAYOUT, 40.0)
    assert out == {"a": [], "b": [pytest.approx(15.0)]}


def test_aligned_start():
    """Admitted at 3 ms with offset 5 on a 20 ms circle: its first comm
    phase would begin at 3 + 10 = 13, so it starts 12 ms late, at 15;
    comm phases then begin at 25, 45, 65 and end 10 ms later."""
    ans = _answers({"a": (5.0, 20.0)})
    out = reference.follow({"a": LOW}, {"t_ms": 0.0, "jobs": {}},
                           [(3.0, "a", W01, ans)], {"a": 1e9}, LAYOUT, 80.0,
                           control=[(3.0, ans)])
    assert out["a"] == pytest.approx([35.0, 55.0, 75.0])


def test_injected_idle_lengthens_every_compute_phase():
    """5 ms injected on a 25 ms circle at offset 0: compute takes 15 ms;
    admitted at 0, the first comm phase would begin at 15, so the start
    waits 10 ms; comm phases begin at 25, 50, 75."""
    ans = _answers({"a": (0.0, 25.0)}, {"a": 5.0})
    out = reference.follow({"a": LOW}, {"t_ms": 0.0, "jobs": {}},
                           [(0.0, "a", W01, ans)], {"a": 1e9}, LAYOUT, 90.0,
                           control=[(0.0, ans)])
    assert out["a"] == pytest.approx([35.0, 60.0, 85.0])


def _running(phase, end, left=None):
    return {"workers": W01, "phase": phase, "end": end, "left": left or {}}


def test_admission_realigns_a_low_priority_job_in_compute():
    """a (low) computes until 7 ms; h (high, one worker: no flows) too.
    b is admitted at 2 ms: a's compute end moves to the next t = 0 (mod
    20), 20 ms, and it pauses; h is never paused, and b is new."""
    ans = _answers({"a": (0.0, 20.0), "h": (0.0, 20.0)})
    h = {"workers": ["w2", "w2"], "phase": "compute", "end": 7.0, "left": {}}
    start = {"t_ms": 0.0, "control": ans,
             "jobs": {"a": _running("compute", 7.0), "h": h}}
    out = reference.follow({"a": LOW, "h": HIGH, "b": LOW}, start,
                           [(2.0, "b", ["w2", "w2"], ans)],
                           {"a": 1e9, "h": 1e9, "b": 1e9}, LAYOUT, 60.0,
                           control=[(2.0, ans)])
    assert out["a"] == pytest.approx([30.0, 50.0])
    assert out["h"] == pytest.approx([17.0, 37.0, 57.0])
    assert out["b"] == pytest.approx([22.0, 42.0])


def test_admission_realign_in_comm_waits_for_the_next_compute_phase():
    """a moves its last 0.1 Gb per NIC at 20 Gbps, ending at 5 ms; b's
    admission at 2 ms leaves a realign pending, so the compute phase from
    5 ms ends at the next t = 0 (mod 20) after 15 ms: 20 ms."""
    ans = _answers({"a": (0.0, 20.0)})
    start = {"t_ms": 0.0, "control": ans, "jobs": {
        "a": _running("comm", None, {"w0": 0.1, "w1": 0.1})}}
    out = reference.follow({"a": LOW, "b": LOW}, start,
                           [(2.0, "b", ["w2", "w2"], ans)],
                           {"a": 1e9, "b": 1e9}, LAYOUT, 60.0,
                           control=[(2.0, ans)])
    assert out["a"] == pytest.approx([5.0, 30.0, 50.0])


def test_reported_realign_and_a_new_offset():
    """No admission: a drift report asks at 3 ms to realign a, computing
    until 7; it pauses until 20.  At 35 ms the controller moves a's offset
    to 12 and a report at 36 (a computes from 30 until 40) asks again: its
    compute end moves to 52."""
    first = _answers({"a": (0.0, 20.0)})
    moved = _answers({"a": (12.0, 20.0)})
    start = {"t_ms": 0.0, "control": first,
             "jobs": {"a": _running("compute", 7.0)}}
    out = reference.follow({"a": LOW}, start, [], {"a": 1e9}, LAYOUT, 80.0,
                           control=[(35.0, moved)],
                           realigns=[(3.0, ["a"]), (36.0, ["a"])])
    assert out["a"] == pytest.approx([30.0, 62.0])
