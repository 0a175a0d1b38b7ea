"""End-to-end dynamic event-loop throughput on a 10k-job production trace.

Drive the FULL dynamic simulation (DESIGN.md section 17) — online arrivals
with queueing and eviction, :class:`JobDeparture` truncation, synthetic
background/capacity/traffic events (including unknown-target offenders,
exercising the structured warnings), stop-and-wait reconfiguration ON —
over a :func:`~repro.core.trace.generate_production_trace` trace
compressed onto an oversubscribed leaf–spine fabric, and time
``ClusterSimulator.run()`` on two fluid backends:

  * ``python`` — the float64 oracle backend (its results are pinned
    bit-for-bit by ``tests/goldens/sim_goldens.json``).
  * ``jnp`` — dirty affinity components batched through one
    shape-bucketed ``fluid.fill_corpus`` per tick; sampled in-loop solves
    are re-solved with ``fill_python`` for ``max_abs_err_vs_oracle``
    (<=1e-6 acceptance), and the corpus bucket occupancy rides along so
    batch-padding waste is visible.

Rows land in ``BENCH_dynamic_throughput.json`` (run.py ``--dynamic-out``);
``scripts/diff_bench.py --max-abs-err`` gates the oracle error in CI.
Per-phase ``SimConfig.profile`` timings are attached to every row and also
emitted as ``common.RECORDED_EMITS`` timing rows.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Tuple

import numpy as np

from repro.configs.metronome_testbed import MODEL_FLEET
from repro.core import events as events_mod
from repro.core import fluid
from repro.core.cluster import make_fabric_cluster
from repro.core.experiment import Policy, Scenario, build_scheduler
from repro.core.framework import SchedulingFramework
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.core.topology import uplink_id
from repro.core.trace import (TraceJobSpec, generate_production_trace,
                              trace_departure_events, trace_job_name,
                              trace_to_jobs)
from repro.core.workload import Workload

from . import common
from .common import emit, record_dynamic_row

# Small leaf-spine fabric + short heavy-tailed job durations: Metronome
# admission costs O(pods x nodes x active jobs) and is SHARED by every
# row, so the fabric and the trace's active concurrency are sized down
# until shared scheduling is a rounding error and wall clock is dominated
# by the event loop itself — the thing this bench times.  The trace's
# diurnal peak still oversubscribes the 16 chips (queueing + eviction
# retries run), and 2-leaf placements push flows over the 2:1 uplinks
# (multi-link progressive fills + single<->multi mode flips).
N_LEAVES = 2
HOSTS_PER_LEAF = 2
OVERSUBSCRIPTION = 2.0

# short-duration production trace: ~3-4 concurrently active jobs on
# average (vs 16-chip capacity) out of 10k total — the loop's per-tick
# cost scales with the active set, not with the jobs admitted so far
TRACE_KW = dict(median_duration_s=20.0, duration_sigma=1.0,
                duration_clip_s=(8.0, 80.0), task_multipliers=(1, 2),
                task_weights=(0.85, 0.15))

# trace compression: the 24 h submission window plays out in
# ~horizon * TIME_SCALE simulated seconds; iteration counts (and thus
# event-loop ticks) scale with it — 0.06 gives the median job ~6-16
# comm/compute iterations before its departure event truncates it.
# Tick count is also the Amdahl lever against the SHARED per-admission
# scheduling cost every row pays identically (~1.25 ms/tick amortized at
# 0.03); 0.06 doubles the ticks over the same 10k admissions so the loop
# itself dominates wall clock.
TIME_SCALE = 0.06

# synthetic dynamic-environment events: periodic background ramps and
# capacity dips on a few links, traffic changes on real jobs, plus
# unknown-target offenders (one bad link, one bad job name — the
# structured-warning path runs in the timed loop, once per offender)
N_EVENT_BURSTS = 24


def synthetic_events(trace: Tuple[TraceJobSpec, ...], horizon_ms: float,
                     time_scale: float) -> List[events_mod.Event]:
    """Deterministic bg/capacity/traffic bursts across the run."""
    evs: List[events_mod.Event] = []
    hosts = [f"leaf{k}-host0" for k in range(min(4, N_LEAVES))]
    uplink = uplink_id("leaf0")
    for b in range(N_EVENT_BURSTS):
        t0 = horizon_ms * (b + 0.25) / N_EVENT_BURSTS
        t1 = horizon_ms * (b + 0.75) / N_EVENT_BURSTS
        host = hosts[b % len(hosts)]
        evs.append(events_mod.BackgroundFlowChange(t0, link=host,
                                                   rate_gbps=8.0))
        evs.append(events_mod.BackgroundFlowChange(t1, link=host,
                                                   rate_gbps=0.0))
        if b % 3 == 0:
            evs.append(events_mod.LinkCapacityChange(
                t0, link=uplink, allocatable_gbps=0.6 * HOSTS_PER_LEAF
                * 25.0 / OVERSUBSCRIPTION))
            evs.append(events_mod.LinkCapacityChange(
                t1, link=uplink, allocatable_gbps=None,
                capacity_gbps=HOSTS_PER_LEAF * 25.0 / OVERSUBSCRIPTION))
        if b % 4 == 0 and trace:
            ji = (b * 37) % len(trace)
            evs.append(events_mod.TrafficChange(
                t0, job=trace_job_name(trace[ji], ji),
                duty_mult=1.25 if b % 8 else 0.8))
    # unknown-target offenders: ignored (with ONE structured warning each)
    evs.append(events_mod.BackgroundFlowChange(horizon_ms * 0.1,
                                               link="ghost-host",
                                               rate_gbps=5.0))
    evs.append(events_mod.BackgroundFlowChange(horizon_ms * 0.2,
                                               link="ghost-host",
                                               rate_gbps=9.0))
    evs.append(events_mod.TrafficChange(horizon_ms * 0.15, job="ghost-job",
                                        duty_mult=2.0))
    return evs


@dataclasses.dataclass(frozen=True)
class DynamicTraceBuild:
    """Picklable build: production trace + departures + synthetic events on
    the oversubscribed bench fabric."""

    trace: Tuple[TraceJobSpec, ...]
    time_scale: float = TIME_SCALE

    def __call__(self):
        cluster = make_fabric_cluster(
            n_leaves=N_LEAVES, hosts_per_leaf=HOSTS_PER_LEAF,
            bw_gbps=25.0, oversubscription=OVERSUBSCRIPTION)
        jobs = trace_to_jobs(list(self.trace), MODEL_FLEET,
                             time_scale=self.time_scale, open_ended=True)
        wls = []
        for j in jobs:
            wl = Workload(name=j.name, jobs=[j])
            j.workload = wl.name
            for t in j.tasks:
                t.workload = wl.name
            wls.append(wl)
        horizon_ms = max(
            (s.submit_time_s + s.duration_s) for s in self.trace
        ) * self.time_scale * 1e3
        events = list(trace_departure_events(list(self.trace),
                                             time_scale=self.time_scale))
        events.extend(synthetic_events(self.trace, horizon_ms,
                                       self.time_scale))
        return cluster, wls, (), events


def _horizon_ms(trace, time_scale: float) -> float:
    return max((s.submit_time_s + s.duration_s) for s in trace) \
        * time_scale * 1e3


def run_trace_sim(scen: Scenario, policy: Policy, cfg: SimConfig):
    """The experiment.run TRACE branch, opened up so the bench can reach
    the live simulator (fluid-engine sampling, corpus stats) and time
    ``run()`` alone — identical construction for every row."""
    if (policy.sim_backend is not None
            and cfg.fluid_backend != policy.sim_backend):
        cfg = dataclasses.replace(cfg, fluid_backend=policy.sim_backend)
    cluster, workloads, background, events = scen.materialize()
    plugin, controller = build_scheduler(policy)
    fw = SchedulingFramework(cluster.copy(), plugin)
    sim = ClusterSimulator(
        fw.cluster, [], cfg, controller=controller, background=background,
        registry=fw.registry, framework=fw, arrivals=workloads,
        events=events, offline_recalc=not policy.skip_third_stage,
    )
    return sim, len(events)


def _emit_profile(name: str, prof) -> None:
    ticks = max(1, prof.ticks)
    for phase, secs in prof.phase_seconds().items():
        emit(f"{name}_{phase}", secs * 1e6 / ticks,
             f"ticks={prof.ticks};solves={prof.solves};"
             f"skipped={prof.skipped_assigns};"
             f"events={prof.events_applied}")


def run() -> None:
    n_jobs = common.pick(10_000, 250)
    trace = tuple(generate_production_trace(MODEL_FLEET, n_jobs=n_jobs,
                                            seed=7, **TRACE_KW))
    time_scale = TIME_SCALE
    duration_ms = _horizon_ms(trace, time_scale) + 1_000.0
    scen = Scenario.trace(name="dynamic-trace",
                          build=DynamicTraceBuild(trace, time_scale))
    # skip_third_stage: per-admission offline recalculation is shared
    # (identical) work for every row — off, so the loop dominates.
    # rotation_joint=False: the joint offset planner is EXPONENTIAL in
    # affinity-component size (a single 7-job overlap costs minutes of
    # exhaustive combo search); the legacy uplink-wins reconciliation keeps
    # admission O(link) while stop-and-wait reconfiguration stays ON.
    policy = Policy("metronome", skip_third_stage=True,
                    rotation_joint=False)
    base_cfg = SimConfig(duration_ms=duration_ms, seed=3, jitter_std=0.01,
                         profile=True)

    for backend in ("python", "jnp"):
        row_policy = (policy if backend == "python"
                      else dataclasses.replace(policy, sim_backend=backend))
        sim, n_events = run_trace_sim(scen, row_policy, base_cfg)
        if backend != "python":
            sim.fluid.sample_stride = 7  # audit in-loop solves vs oracle
        t0 = time.perf_counter()
        res = sim.run()
        seconds = time.perf_counter() - t0
        err = 0.0
        corpus = None
        if backend != "python":
            for d, p, c, rates in sim.fluid.samples:
                gold = fluid.fill_python(np.asarray(d, dtype=float), p, c)
                if len(gold):
                    err = max(err, float(np.max(np.abs(rates - gold))))
            corpus = sim.fluid.corpus_stats.as_dict()
            emit(f"dynamic_corpus_{backend}",
                 sim.fluid.corpus_stats.flow_occupancy * 100.0,
                 f"flow_occupancy_pct;buckets={corpus['buckets']};"
                 f"link_occupancy={corpus['link_occupancy']:.3f}")
        name = f"dynamic_loop_array_{backend}"
        prof = res.profile
        record_dynamic_row(
            name=name, backend=backend, n_jobs=n_jobs,
            n_events=n_events, ticks=prof.ticks, seconds=seconds,
            max_abs_err_vs_oracle=err,
            profile=prof.as_dict(), corpus=corpus)
        emit(name, seconds * 1e6 / max(1, prof.ticks),
             f"n_jobs={n_jobs};seconds={seconds:.2f};"
             f"max_abs_err={err:.3g}")
        _emit_profile(name, prof)
