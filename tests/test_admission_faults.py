"""Metronome's admission and controller against the rules of Sec. III-B
and Algorithm 1, on hand-built cases: a period no circle takes (fault 1),
idle only into low-priority jobs (fault 2), one circle for contended links
sharing a job and none once the contention ends (fault 3), the offline
recalculation at the circle's Eq. 18 optimum (fault 4), the Score phase's
loop check and joint problem (fault 5), and a chunked run (fault 6).
Numbers in the comments follow from the rules by hand."""
import dataclasses

import networkx as nx
import numpy as np
import pytest

from repro.core import geometry, rotation
from repro.core.cluster import Cluster, Node, Resources
from repro.core.contention import LinkView
from repro.core.controller import StopAndWaitController
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin, ReserveMessage, _closes_loop
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.core.workload import HIGH, LOW, make_job


def _cluster(n=2):
    return Cluster([Node(f"n{i}", Resources(cpu=32, mem=256, gpu=4),
                         bw_gbps=25.0) for i in range(n)])


def _framework(n=2):
    ctrl = StopAndWaitController()
    fw = SchedulingFramework(_cluster(n), MetronomePlugin(controller=ctrl))
    return fw, ctrl


def _job(name, period, duty, bw, prio, n_tasks=1, submit=0.0):
    return make_job(name, n_tasks=n_tasks, period_ms=period, duty=duty,
                    bw_gbps=bw, priority=prio, submit_time_s=submit)


def _place(fw, job, nodes):
    """Deploy ``job``'s pods on ``nodes`` as Reserve would, and tell the
    controller (no link asks for the third stage)."""
    fw.registry.jobs[job.name] = job
    for t, n in zip(job.tasks, nodes):
        t.node = n
        fw.cluster.node(n).allocate(t.uid, t.resources, t.traffic.bw_gbps)
        fw.registry.tasks[t.uid] = t
    fw.cluster.bump_epoch()
    fw.registry.bump()
    fw.plugin.controller.on_schedule(
        fw.cluster, fw.registry,
        ReserveMessage(node=nodes[0], schemes={}, shifts_ms={},
                       skip_phase_three=True))


# ---------------------------------------------------------------- fault 1
def test_a_period_no_circle_takes_is_flagged_and_left_unanswered():
    """AlexNet (45 ms, high) is the reference; GPT-1's 424 ms is 26 ms
    short of the 450 ms circle (ten AlexNet periods), within E_T (42.4
    ms), so it takes 26 ms of idle; BERT's 527 ms is 77 ms over 450 and
    fits no base of 1 to 16 reference periods: incompatible.  It keeps
    its own period, so the controller answers it nothing, not 450 ms, and
    a link holding it scores 0."""
    u = geometry.unify_periods([45.0, 424.0, 527.0], [HIGH, LOW, LOW])
    assert u.base_ms == 450.0
    assert list(u.ok) == [True, True, False]
    assert list(u.injected_ms) == [0.0, 26.0, 0.0]
    fw, ctrl = _framework(1)
    jobs = [_job("alexnet", 45.0, 0.5, 24.0, HIGH),
            _job("gpt1", 424.0, 0.17, 20.0, LOW, submit=1.0),
            _job("bert", 527.0, 0.4, 10.0, LOW, submit=2.0)]
    for j in jobs:
        _place(fw, j, ["n0"])
    assert ctrl.job_alignment("alexnet") == (0.0, 45.0)
    assert ctrl.job_alignment("gpt1")[1] == 450.0
    assert ctrl.job_alignment("bert") is None
    assert ctrl.injected_ms == {"gpt1": 26.0}
    view = LinkView.from_registry(fw.cluster, fw.registry)
    assert rotation.solve_link(view, fw.registry, "n0")[0] == 0.0


def test_a_high_priority_job_off_every_circle_keeps_its_period():
    """A high-priority 600 ms job beside a high-priority 96 ms reference:
    576 and 672 ms leave it 24 and 72 ms off, and a high-priority job
    takes no idle, so it is on no circle and answered nothing."""
    assert list(geometry.unify_periods([96.0, 600.0], [HIGH, HIGH]).ok) == [
        True, False]
    fw, ctrl = _framework(1)
    _place(fw, _job("vgg19", 96.0, 0.48, 25.0, HIGH), ["n0"])
    _place(fw, _job("gpt2", 600.0, 0.5, 22.0, HIGH, submit=1.0), ["n0"])
    assert ctrl.job_alignment("vgg19") == (0.0, 96.0)
    assert ctrl.job_alignment("gpt2") is None


# ---------------------------------------------------------------- fault 2
def test_equal_priorities_slow_no_high_priority_job():
    """Two high-priority jobs, 45 and 96 ms: 96 ms is never slowed, so
    the first base that merges it is 495 ms (eleven 45 ms periods), five
    arcs of 99 ms, 3 ms off (within G_T); nothing is injected."""
    u = geometry.unify_periods([45.0, 96.0], [HIGH, HIGH])
    assert u.base_ms == 495.0
    assert list(u.muls) == [11, 5]
    assert list(u.periods_ms) == [45.0, 99.0]
    assert list(u.injected_ms) == [0.0, 0.0]


# ---------------------------------------------------------------- fault 3
def test_links_sharing_a_job_share_one_circle_until_contention_ends():
    """a (100 ms, high) runs on n0 and n1; b (92 ms, low) beside it on n0
    and c (130 ms, low) on n1, each pair over the 25 Gbps NICs.  One job
    runs one period, so both links take one circle over a, b and c: 400
    ms, a and b four 100 ms arcs (b with 8 ms of idle), c three of 133.3
    ms.  Once c leaves, n1 no longer contends: the circle is n0's alone,
    100 ms.  Once b leaves too, nothing contends: no answer, no idle."""
    fw, ctrl = _framework(2)
    a = _job("a", 100.0, 0.4, 20.0, HIGH, n_tasks=2)
    b = _job("b", 92.0, 0.4, 20.0, LOW, submit=1.0)
    c = _job("c", 130.0, 0.3, 20.0, LOW, submit=2.0)
    _place(fw, a, ["n0", "n1"])
    _place(fw, b, ["n0"])
    _place(fw, c, ["n1"])
    assert {l: st.scheme.base_ms for l, st in ctrl.links.items()} == {
        "n0": 400.0, "n1": 400.0}
    assert ctrl.job_alignment("a")[1] == 100.0
    assert ctrl.job_alignment("b")[1] == 100.0
    assert ctrl.job_alignment("c")[1] == pytest.approx(400.0 / 3)
    assert ctrl.injected_ms["b"] == 8.0
    fw.evict_job(c)
    assert list(ctrl.links) == ["n0"]
    assert ctrl.links["n0"].scheme.base_ms == 100.0
    assert ctrl.job_alignment("c") is None
    assert ctrl.job_alignment("a")[1] == ctrl.job_alignment("b")[1] == 100.0
    fw.evict_job(b)
    assert ctrl.links == {}
    assert ctrl.job_alignment("a") is None
    assert ctrl.injected_ms == {}


# ---------------------------------------------------------------- fault 4
def _eq18(patterns, bw, shifts, cap):
    total = np.einsum("p,ps->s", np.asarray(bw, float),
                      geometry.roll_patterns(patterns, shifts))
    return 100.0 * (1 - np.maximum(total - cap, 0).sum() / (cap * 72))


def test_offline_recalculation_answers_at_the_circles_optimum():
    """j0 (110 ms) on n0, j1 (110 ms) on n0 and n1, j2 (92 ms) on n1, all
    low, 18-20 Gbps each: both NICs contend and share j1, so one 440 ms
    circle (j0 and j1 four arcs, j2 five).  After the third stage each
    NIC scores, at the answered offsets and over the pods' traffic on it
    (18 Gbps of j1 on each, not its whole 36), no less than the best that
    any shifts give the worse of the two."""
    fw, ctrl = _framework(2)
    jobs = [_job("j0", 110.0, 0.25, 18.0, LOW),
            _job("j1", 110.0, 0.25, 18.0, LOW, n_tasks=2, submit=0.5),
            _job("j2", 92.0, 0.5, 20.0, LOW, submit=1.0)]
    for j, nodes in zip(jobs, (["n0"], ["n0", "n1"], ["n1"])):
        _place(fw, j, nodes)
        ctrl.pending_recalc.extend(nodes)
    ctrl.run_offline_recalculation(fw.registry, fw.cluster)
    u = geometry.unify_periods([110.0, 110.0, 92.0], [LOW, LOW, LOW])
    assert (u.base_ms, list(u.muls)) == (440.0, [4, 4, 5])
    pats = geometry.pattern_matrix(
        u.muls, [j.traffic.comm_ms / e for j, e in zip(jobs, u.periods_ms)])
    rows = {"n0": [18.0, 18.0, 0.0], "n1": [0.0, 18.0, 20.0]}
    best = max(min(_eq18(pats, bw, np.array([0, s1, s2]), 25.0)
                   for bw in rows.values())
               for s1 in range(72 // 4) for s2 in range(72 // 5))
    for lid, bw in rows.items():
        sch = ctrl.links[lid].scheme
        assert sch.base_ms == 440.0
        shifts = np.array([ctrl.global_offsets_ms[j.name] / 440.0 * 72
                           for j in jobs])
        assert np.allclose(shifts, np.round(shifts))
        score = _eq18(pats, bw, np.round(shifts).astype(int), 25.0)
        assert score >= best - 1e-9


# ---------------------------------------------------------------- fault 5
def test_a_loop_that_sums_basis_cycles_is_found():
    """a-b-c-d-a with the chord b-d: a-b, b-d and d-a share link L1, b-c,
    c-d and d-b share L2, so each basis cycle keeps one link and is
    harmless; the outer cycle a-b-c-d-a has no link on all its edges and
    cannot be given offsets link by link."""
    g = nx.Graph()
    for u, v, links in (("a", "b", {"L1"}), ("b", "d", {"L1", "L2"}),
                        ("d", "a", {"L1"}), ("b", "c", {"L2"}),
                        ("c", "d", {"L2"})):
        g.add_edge(u, v, links=links)
    assert _closes_loop(g, "a")
    g["d"]["a"]["links"] = {"L1", "L2"}
    g["a"]["b"]["links"] = {"L1", "L2"}
    assert not _closes_loop(g, "a")


def test_the_joint_problem_holds_the_candidate_pod():
    """The candidate is the first pod of its job: the joint problem over
    the two NICs still holds that job, at its own traffic."""
    fw, _ = _framework(2)
    _place(fw, _job("a", 100.0, 0.4, 20.0, HIGH, n_tasks=2), ["n0", "n1"])
    new = _job("b", 100.0, 0.4, 20.0, LOW, n_tasks=2, submit=1.0)
    fw.registry.jobs[new.name] = new
    view = LinkView.from_registry(fw.cluster, fw.registry,
                                  extra=new.tasks[0], extra_node="n0")
    res = rotation.joint_solve(view, fw.registry, ["n0"])
    assert res is not None and res.jobs == ["a", "b"]
    assert res.score == 100.0


# ---------------------------------------------------------------- fault 6
@pytest.mark.parametrize("backend", ["python", "jnp"])
def test_a_run_cut_where_a_flow_ends_resumes_on_time(backend):
    """A 100 ms job sends 40 ms at full rate after 60 ms of compute, so
    its flows end at 100 ms, 200 ms, ...: runs cut at those edges step
    it there, as one run does."""
    def sim():
        j = make_job("a", n_tasks=2, period_ms=100.0, duty=0.4,
                     bw_gbps=10.0, n_iterations=50)
        j.tasks[0].node, j.tasks[1].node = "n0", "n1"
        return ClusterSimulator(_cluster(2), [j], SimConfig(
            duration_ms=300.0, seed=0, jitter_std=0.0,
            fluid_backend=backend))

    whole = sim().run()
    cut = sim()
    for t in (100.0, 200.0, 300.0):
        cut.config = dataclasses.replace(cut.config, duration_ms=t)
        res = cut.run()
    assert whole.durations_ms == {"a": [100.0, 100.0]}
    assert res.durations_ms == whole.durations_ms
