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
