"""Fabric-wide joint rotation planner (core/rotation.py) tests.

Three pillars (ISSUE 3 acceptance):

  * star-topology equivalence — the planner must reduce BIT-FOR-BIT to the
    legacy per-link solve + BFS offset merge (oracle: verbatim copy of the
    pre-planner controller's ``_recompute_global_offsets``);
  * the J1 conflict oracle — per-link solves provably conflict; the legacy
    "uplinks win" reconciliation leaves a host link oversubscribed in time
    while the joint solve is feasible on every link;
  * kernel parity — the stacked (L, R, S) multi-link score core matches the
    jnp reference and the per-link numpy min in interpret mode.
"""
import networkx as nx
import numpy as np
import pytest

from repro.configs.metronome_testbed import make_snapshot
from repro.core import geometry, rotation, scoring
from repro.core.contention import LinkView
from repro.core.controller import StopAndWaitController
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin
from repro.core.topology import is_uplink


# ---------------------------------------------------------------------------
# Legacy oracle: verbatim copy of the pre-planner controller's offset merge
# (BFS over the affinity graph, add_edge overwrite, uplinks-LAST tie-break)
# ---------------------------------------------------------------------------

def legacy_recompute_global_offsets(links, priorities, di_pre):
    g = nx.Graph()
    link_shift_ms = {}
    ordered = sorted(links.items(),
                     key=lambda kv: (is_uplink(kv[0]), kv[0]))
    for node, state in ordered:
        sch = state.scheme
        delays = geometry.shifts_to_delay_ms(sch.shifts_slots, sch.base_ms,
                                             di_pre)
        for j, d in zip(sch.jobs, delays):
            link_shift_ms[(node, j)] = float(d)
            g.add_node(j)
        for i in range(len(sch.jobs)):
            for k in range(i + 1, len(sch.jobs)):
                a, b = sch.jobs[i], sch.jobs[k]
                rel = link_shift_ms[(node, b)] - link_shift_ms[(node, a)]
                g.add_edge(a, b, rel=rel, src=a)
    offsets = {}
    for comp in nx.connected_components(g):
        comp = list(comp)
        ref = sorted(comp, key=lambda j: (-priorities.get(j, 0), j))[0]
        offsets[ref] = 0.0
        for u, v in nx.bfs_edges(g, ref):
            rel = g[u][v]["rel"]
            if g[u][v]["src"] != u:
                rel = -rel
            offsets[v] = offsets[u] + rel
    return offsets


def schedule_snapshot(sid, joint=True):
    cluster, wls, bg = make_snapshot(sid, n_iterations=100)
    ctrl = StopAndWaitController(joint=joint)
    fw = SchedulingFramework(cluster, MetronomePlugin(controller=ctrl,
                                                      joint=joint))
    for wl in wls:
        fw.schedule_workload(wl)
    return cluster, fw, ctrl


def offsets_implied_scores(cluster, registry, ctrl, demand="planning"):
    """Per-link Eq. 18 score of the controller's FINAL global offsets."""
    view = LinkView.from_registry(cluster, registry)
    out = {}
    for lid, st in ctrl.links.items():
        sch = st.scheme
        duties, rbws = view.recalc_traffic(lid, sch.jobs, sch.muls,
                                           sch.base_ms)
        if demand == "planning":
            groups = view.link_groups(lid)
            bws = [sum(t.traffic.bw_gbps for t in groups.get(j, []))
                   for j in sch.jobs]
        else:
            bws = rbws
        pats = geometry.pattern_matrix(sch.muls, duties, ctrl.di_pre)
        shifts = np.array([
            geometry.delay_to_shift_slots(ctrl.job_offset_ms(j), sch.base_ms,
                                          ctrl.di_pre)
            for j in sch.jobs
        ])
        out[lid] = float(scoring.score_combos(
            pats, np.asarray(bws), cluster.link_alloc(lid),
            shifts[None, :])[0])
    return out


# ---------------------------------------------------------------------------
# Star-topology equivalence (bit-for-bit)
# ---------------------------------------------------------------------------

class TestStarEquivalence:
    @pytest.mark.parametrize("sid", ["S1", "S2", "S4"])
    def test_offsets_match_legacy_oracle(self, sid):
        """The planner's resolution equals the legacy BFS merge bit-for-bit
        on the star snapshots.  S2/S4 components are consistent so the
        joint path never fires; on S1 the three identical jobs produce a
        conflict the legacy merge silently overwrote — the joint re-solve
        lands on the same offsets (symmetric problem), pinning that the
        replacement is behavior-preserving there too."""
        cluster, fw, ctrl = schedule_snapshot(sid)
        want = legacy_recompute_global_offsets(ctrl.links, ctrl._priorities,
                                               ctrl.di_pre)
        assert ctrl.global_offsets_ms == want
        if sid in ("S2", "S4"):
            assert ctrl.joint_resolve_count == 0  # nothing conflicted

    def test_single_link_plan_equals_per_link_solver(self):
        """plan() over one contended link == find_feasible_rotation on it."""
        cluster, fw, ctrl = schedule_snapshot("S2")
        view = LinkView.from_registry(cluster, fw.registry)
        for lid, st in ctrl.links.items():
            score, scheme = rotation.solve_link(view, fw.registry, lid,
                                                mode="fast")
            assert scheme is not None
            res = rotation.plan(view, fw.registry, links=[lid], mode="fast")
            assert np.array_equal(res.schemes[lid].shifts_slots,
                                  scheme.shifts_slots)
            assert res.score == score

    def test_joint_flag_irrelevant_on_star(self):
        """joint=True and joint=False are identical end-to-end on stars."""
        _, fw_a, ctrl_a = schedule_snapshot("S2", joint=True)
        _, fw_b, ctrl_b = schedule_snapshot("S2", joint=False)
        assert ctrl_a.global_offsets_ms == ctrl_b.global_offsets_ms
        for lid in ctrl_a.links:
            assert np.array_equal(ctrl_a.links[lid].scheme.shifts_slots,
                                  ctrl_b.links[lid].scheme.shifts_slots)


# ---------------------------------------------------------------------------
# J1: per-link solves conflict; joint solve feasible, legacy merge not
# ---------------------------------------------------------------------------

class TestJointConflictOracle:
    def test_per_link_solves_conflict(self):
        """Host-optimal relative shift of (hi, lo) is infeasible on the
        shared uplink: the per-link solutions genuinely disagree."""
        cluster, fw, ctrl = schedule_snapshot("J1")
        view = LinkView.from_registry(cluster, fw.registry)
        rels = {}
        for lid in view.planning_links():
            score, scheme = rotation.solve_link(view, fw.registry, lid,
                                                mode="fast")
            if scheme is None or not {"j1-hi", "j1-lo"} <= set(scheme.jobs):
                continue
            d = geometry.shifts_to_delay_ms(scheme.shifts_slots,
                                            scheme.base_ms, ctrl.di_pre)
            rel = (d[scheme.jobs.index("j1-lo")]
                   - d[scheme.jobs.index("j1-hi")])
            rels[lid] = round(float(rel), 6)
        host_rels = {v for k, v in rels.items() if not is_uplink(k)}
        uplink_rels = {v for k, v in rels.items() if is_uplink(k)}
        assert host_rels and uplink_rels
        assert host_rels.isdisjoint(uplink_rels)

    def test_joint_feasible_where_legacy_is_not(self):
        cluster_j, fw_j, ctrl_j = schedule_snapshot("J1", joint=True)
        scores_j = offsets_implied_scores(cluster_j, fw_j.registry, ctrl_j)
        assert ctrl_j.joint_resolve_count >= 1
        assert min(scores_j.values()) == pytest.approx(100.0)

        cluster_l, fw_l, ctrl_l = schedule_snapshot("J1", joint=False)
        scores_l = offsets_implied_scores(cluster_l, fw_l.registry, ctrl_l)
        assert min(scores_l.values()) < 100.0 - 1e-6

    def test_legacy_oracle_matches_joint_false(self):
        """joint=False IS the legacy reconciliation (oracle-pinned)."""
        cluster, fw, ctrl = schedule_snapshot("J1", joint=False)
        want = legacy_recompute_global_offsets(ctrl.links, ctrl._priorities,
                                               ctrl.di_pre)
        assert ctrl.global_offsets_ms == want

    def test_joint_solve_direct(self, interpret_kernels):
        """joint_solve over the full J1 component: feasible on every link,
        reference pinned at zero (Eq. 16), numpy == kernel backend (the
        multi-link kernel in interpret mode)."""
        cluster, fw, ctrl = schedule_snapshot("J1")
        view = LinkView.from_registry(cluster, fw.registry)
        links = [l for l in view.planning_links()
                 if rotation.solve_link(view, fw.registry, l)[1] is not None]
        ran = interpret_kernels[("score_multilink", "interpret")]
        res_np = rotation.joint_solve(view, fw.registry, links,
                                      backend="numpy")
        res_k = rotation.joint_solve(view, fw.registry, links,
                                     backend="kernel")
        assert res_np.feasible
        assert res_np.jobs[0] == "j1-hi" and res_np.shifts[0] == 0
        assert interpret_kernels[("score_multilink", "interpret")] == ran + 1
        assert np.array_equal(res_np.shifts, res_k.shifts)
        assert res_np.score == pytest.approx(res_k.score, abs=1e-4)


# ---------------------------------------------------------------------------
# Multi-link kernel parity (interpret mode)
# ---------------------------------------------------------------------------

class TestMultilinkKernelParity:
    def _problem(self, seed=0, l=3):
        rng = np.random.default_rng(seed)
        pats = geometry.pattern_matrix([1, 1, 2], [0.3, 0.25, 0.2], 72)
        banks = scoring.rolled_bank(pats, [1, 24, 36])
        bw = rng.uniform(5.0, 20.0, size=(l, 3))
        caps = rng.uniform(18.0, 30.0, size=l)
        base = bw[:, 0:1] * pats[0][None, :]
        bank_a = bw[:, 1, None, None] * banks[1][None]
        bank_b = bw[:, 2, None, None] * banks[2][None]
        return pats, banks, bw, caps, base, bank_a, bank_b

    def test_interpret_matches_ref(self):
        from repro.kernels import ops as kops
        from repro.kernels import ref
        _, _, _, caps, base, bank_a, bank_b = self._problem()
        got = kops.score_multilink(base, bank_a, bank_b, caps,
                                   interpret=True)
        want = np.asarray(ref.metronome_score_multilink_ref(
            base, bank_a, bank_b, caps))
        assert got.shape == (24, 36)
        assert np.allclose(got, want, atol=1e-4)

    def test_ref_matches_per_link_numpy_min(self):
        from repro.kernels import ref
        pats, banks, bw, caps, base, bank_a, bank_b = self._problem(seed=1)
        want = np.asarray(ref.metronome_score_multilink_ref(
            base, bank_a, bank_b, caps)).reshape(-1)
        combos = scoring.lex_combos([1, 24, 36], 0, 24 * 36)
        per = None
        for li in range(len(caps)):
            s = scoring.score_combos(pats, bw[li], float(caps[li]), combos,
                                     banks)
            per = s if per is None else np.minimum(per, s)
        assert np.allclose(want, per, atol=1e-4)

    def test_single_link_reduces_to_pairwise(self):
        from repro.kernels import ops as kops
        _, _, _, caps, base, bank_a, bank_b = self._problem(l=1)
        multi = kops.score_multilink(base, bank_a, bank_b, caps[:1],
                                     interpret=True)
        pair = kops.score_pairwise(base[0], bank_a[0], bank_b[0],
                                   float(caps[0]), interpret=True)
        assert np.allclose(multi, pair, atol=1e-4)
