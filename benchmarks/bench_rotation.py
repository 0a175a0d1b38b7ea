"""Fabric-wide joint rotation planner vs the legacy per-link tie-break.

Two comparisons (see DESIGN.md section 13):

  * **Scheme quality on J1** — the oracle snapshot where per-link rotation
    solves provably conflict (host-optimal shift infeasible on the shared
    uplink).  ``rotation_joint=False`` reproduces the pre-planner
    "uplinks take precedence" reconciliation; we report the worst per-link
    planning-demand score of the final global offsets (joint: 100 = every
    link feasible; legacy: < 100 = a host link stays oversubscribed in
    time) and the resulting JCT delta of the squeezed low-priority job.

  * **Planner wall-time at F4 scale** — the Score-phase solve of the F4
    uplink component (3 jobs x 2 contended links, 5184 rotation combos):
    the legacy per-link pipeline (one ``find_feasible_rotation`` per link,
    per-combo Python run scan) vs the planner's batched multi-link path
    (stacked (L, R, S) banks through ``kernels.ops.score_multilink``, the
    compiled Pallas kernel, plus the vectorized run scan).  The derived
    field reports the speedup; the acceptance bar is >= 5x.  It needs a
    TPU and is left out elsewhere.
"""
from __future__ import annotations

import time

import numpy as np

from repro.configs.metronome_testbed import make_snapshot, snapshot_scenario
from repro.core import geometry, rotation, scoring
from repro.core.contention import LinkView
from repro.core.controller import StopAndWaitController
from repro.core.experiment import Policy
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin
from repro.core.topology import is_uplink

from . import common
from .common import Timer, emit

# the joint planner vs the pre-planner "uplinks take precedence" ablation
J1_POLICIES = (
    Policy("metronome", label="joint"),
    Policy("metronome", rotation_joint=False, label="legacy"),
)


def _worst_planning_score(cluster, registry, ctrl) -> float:
    """Worst per-link Eq. 18 score of the controller's FINAL global offsets
    under the planning demand view — the fabric-feasibility check."""
    view = LinkView.from_registry(cluster, registry)
    worst = 100.0
    for lid, st in ctrl.links.items():
        sch = st.scheme
        duties, _ = view.recalc_traffic(lid, sch.jobs, sch.muls, sch.base_ms)
        pats = geometry.pattern_matrix(sch.muls, duties, ctrl.di_pre)
        shifts = np.array([
            geometry.delay_to_shift_slots(ctrl.job_offset_ms(j), sch.base_ms,
                                          ctrl.di_pre)
            for j in sch.jobs
        ])
        groups = view.link_groups(lid)
        bws = [sum(t.traffic.bw_gbps for t in groups.get(j, []))
               for j in sch.jobs]
        worst = min(worst, float(scoring.score_combos(
            pats, np.asarray(bws), cluster.link_alloc(lid),
            shifts[None, :])[0]))
    return worst


def _schedule(sid: str, joint: bool, n_iterations: int):
    cluster, wls, bg = make_snapshot(sid, n_iterations=n_iterations)
    ctrl = StopAndWaitController(joint=joint)
    fw = SchedulingFramework(cluster, MetronomePlugin(controller=ctrl,
                                                      joint=joint))
    for wl in wls:
        fw.schedule_workload(wl)
    ctrl.run_offline_recalculation(fw.registry, cluster)
    return cluster, fw, ctrl, wls


def _bench_j1() -> None:
    n_iter = common.pick(300, 25)
    cfg = common.bench_cfg(jitter_std=0.02)
    scn = snapshot_scenario("J1", n_iterations=n_iter)
    with Timer() as t:
        sw = common.run_sweep([scn], J1_POLICIES, cfg, origin="rotation")
    for pol in J1_POLICIES:
        # fabric feasibility of the final offsets (planner-internal view)
        cluster, fw, ctrl, _ = _schedule("J1", pol.rotation_joint, n_iter)
        feas = _worst_planning_score(cluster, fw.registry, ctrl)
        r = sw.get("J1", pol.name)
        emit(f"rotation_J1_{pol.name}", t.us / len(J1_POLICIES),
             f"worst_link_score={feas:.2f};"
             f"lo_jct_s={r.sim.finish_times_ms.get('j1-local', np.nan)/1e3:.2f};"
             f"tct_s={r.sim.total_completion_ms/1e3:.2f}")
    lo_j = sw.get("J1", "joint").sim.finish_times_ms.get("j1-local", np.nan)
    lo_l = sw.get("J1", "legacy").sim.finish_times_ms.get("j1-local", np.nan)
    delta = 100.0 * (1.0 - lo_j / lo_l) if lo_l else float("nan")
    emit("rotation_J1_joint_vs_legacy", 0.0,
         f"lo_jct_saving_pct={delta:.2f}")


def _bench_planner_walltime() -> None:
    """Batched multi-link solve vs the per-link Python loop, F4 scale."""
    cluster, fw, ctrl, _ = _schedule("F4", True, common.pick(300, 25))
    view = LinkView.from_registry(cluster, fw.registry)
    links = [l for l in view.planning_links() if is_uplink(l)]
    reps = common.pick(20, 3)

    def loop_path():
        # the legacy Score-phase pipeline: one independent per-link solve
        # (find_feasible_rotation's per-combo Python scan) per link
        out = []
        for lid in links:
            out.append(rotation.solve_link(view, fw.registry, lid,
                                           mode="fast"))
        return out

    def batched_path():
        return rotation.joint_solve(view, fw.registry, links, mode="fast",
                                    backend="kernel")

    loop_path(), batched_path()  # warmup (jit cache for the kernel path)
    t0 = time.perf_counter()
    for _ in range(reps):
        loop_path()
    t_loop = (time.perf_counter() - t0) / reps * 1e6
    t0 = time.perf_counter()
    for _ in range(reps):
        res = batched_path()
    t_batched = (time.perf_counter() - t0) / reps * 1e6
    speedup = t_loop / t_batched if t_batched else float("inf")
    emit("rotation_planner_loop_F4", t_loop,
         f"links={len(links)};combos=5184")
    emit("rotation_planner_batched_F4", t_batched,
         f"links={len(links)};score={res.score:.2f};"
         f"speedup_vs_loop={speedup:.1f}x")


def run() -> None:
    _bench_j1()
    if common.on_tpu():  # the batched path is the multi-link kernel
        _bench_planner_walltime()


if __name__ == "__main__":
    run()
