"""What the harness records of a policy with a stop-and-wait controller,
and how a configuration names its admission reference."""
from __future__ import annotations

import json
import shutil

import pytest

from bench import harness
from bench.probes import Probes
from bench.spec import load_cell

METRONOME = "tiny-metronome.tiny-metro"


def _cell(root, name):
    return load_cell(name, root / "BENCHMARK.json", root / "bench")


def test_attempt_record_holds_what_an_admission_reference_needs(
        tiny_checkout):
    c = _cell(tiny_checkout, METRONOME)
    sim, _ = harness.build(c, 4000000015, backend="python")
    probes = Probes(sim, seed=1, sample_solves=10, trace=False,
                    policy=c.config["policy"])
    harness.advance(sim, 3000.0)
    probes.close()
    recs = probes.admissions
    assert any(r["admitted"] for r in recs)
    for rec in recs:
        assert rec["policy"] == {"scheduler": "metronome"}
        assert set(rec["link_capacity"]) == set(rec["link_alloc"]) == {
            "leaf0-host0", "leaf0-host1", "leaf1-host0", "leaf1-host1",
            "uplink:leaf0", "uplink:leaf1"}
        for task in rec["tasks"]:
            assert set(task) == {"job", "worker", "priority", "period_ms",
                                 "duty", "bw_gbps"}
            assert task["worker"] in rec["nodes"]
        for pod in rec["pods"]:
            assert set(pod) == {"req", "bw", "spread", "job", "priority",
                                "period_ms", "duty"}
            assert pod["job"] == rec["job"]
        assert rec["dependencies"] == []
        n = len(rec["nodes"])
        assert len(rec["latency"]) == n
        assert all(len(row) == n for row in rec["latency"])
        assert {t["job"] for t in rec["tasks"]} | {rec["job"]} <= set(
            rec["submit_s"])
        assert rec["score_params"] == {
            "di_pre": 72, "g_t_ms": 5.0, "e_t_frac": 0.10,
            "rotation_mode": "intermediate", "joint": True}
        for key in ("control_before", "control_after"):
            state = rec[key]
            assert set(state) == {"align", "inject"}
            for offset, period in state["align"].values():
                assert 0.0 <= offset < period
    # the tasks before an attempt are all the pods of jobs admitted earlier
    for i, rec in enumerate(recs):
        earlier = {r["job"] for r in recs[:i] if r["admitted"]}
        jobs = [t["job"] for t in rec["tasks"]]
        assert set(jobs) <= earlier
        assert all(jobs.count(j) == 2 for j in jobs)
    # every attempt's answers are in force from its time on
    assert len(probes.control) >= len(recs)
    admitted = [r for r in recs if r["admitted"]]
    assert any(r["job"] in r["control_after"]["align"] for r in admitted)
    assert any(r["control_after"]["inject"] for r in admitted)


def test_snapshot_carries_the_controller_state(tiny_checkout):
    c = _cell(tiny_checkout, METRONOME)
    sim, _ = harness.build(c, 4000000015, backend="python")
    probes = Probes(sim, seed=1, sample_solves=10, trace=False,
                    policy=c.config["policy"])
    harness.advance(sim, 3000.0)
    edge = harness.snapshot(sim, probes)
    probes.close()
    assert edge["control"] == probes.control_state()
    assert edge["controls"] == len(probes.control)
    assert edge["realigns"] == len(probes.realigns)
    for st in edge["jobs"].values():
        assert st["phase"] in ("waiting", "compute", "paused", "comm")
        assert isinstance(st["pending"], bool)
        assert st["pause"] == 0.0


def test_no_controller_records_no_answers(tiny_checkout):
    c = _cell(tiny_checkout, "tiny-fabric.tiny-peak")
    sim, _ = harness.build(c, 4000000015, backend="python")
    probes = Probes(sim, seed=1, sample_solves=10, trace=False,
                    policy=c.config["policy"])
    harness.advance(sim, 500.0)
    probes.close()
    assert probes.admissions
    assert all(r["control_before"] is None and r["control_after"] is None
               and r["score_params"] is None for r in probes.admissions)
    assert probes.control == [] and probes.realigns == []


def test_unknown_admission_reference_fails_at_load(tiny_checkout, tmp_path):
    shutil.copytree(tiny_checkout / "bench", tmp_path / "bench")
    spec = json.loads((tiny_checkout / "BENCHMARK.json").read_text())
    cfg = json.loads(
        (tiny_checkout / "bench/configs/tiny-metronome.json").read_text())
    cfg["check"]["admission"] = "no_such_reference"
    (tmp_path / "bench/configs/tiny-metronome.json").write_text(
        json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(SystemExit,
                       match=r"no admission/no_such_reference\.py.*"
                             r"least_allocated.*metronome"):
        load_cell(METRONOME, tmp_path / "BENCHMARK.json", tmp_path / "bench")
