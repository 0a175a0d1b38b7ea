"""The array event loop (DESIGN.md section 17).

Four layers of evidence that the vectorized hot path is safe:

  * oracle parity — ``event_loop='array'`` (the default) must reproduce
    ``event_loop='legacy'`` (the pre-array per-object loop, retained
    verbatim) BIT-FOR-BIT on every pinned golden (S1–S5, F2, F4, J1, D1,
    D2) and on an online production-trace run with arrivals/departures;
  * edge cases the vectorized reductions must not regress: starved flows
    with zero rate (no finish event until the duration cap), multiple
    events sharing one timestamp, an arrival tied exactly with an event;
  * structured once-per-offender warnings for events naming unknown
    links/jobs (previously silently dropped);
  * the machinery that rides along: ``SimConfig.profile`` phase counters
    and the program's spans, ``FluidEngine.solve_batch`` memoization, and
    shape-bucketed ``fill_corpus`` batching with occupancy stats.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.configs.metronome_testbed import (DYNAMIC_SNAPSHOTS, MODEL_FLEET,
                                             dynamic_scenario, make_snapshot,
                                             snapshot_scenario)
from repro.core import fluid
from repro.core.events import (BackgroundFlowChange, HostFailure,
                               HostRecovery, JobDeparture, LinkCapacityChange,
                               TrafficChange, UnknownEventTargetWarning)
from repro.core.experiment import Policy, run
from repro.core.cluster import Cluster, Node, Resources
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin
from repro.core.simulator import EPS, COMM, ClusterSimulator, SimConfig
from repro.core.workload import Workload, make_job

CFG = SimConfig(duration_ms=20_000.0, seed=3, jitter_std=0.01)
LEGACY = dataclasses.replace(CFG, event_loop="legacy")

PINNED = ["S1", "S2", "S3", "S4", "S5", "F2", "F4", "J1"]


def _eq(x, y):
    if isinstance(x, float) and isinstance(y, float):
        return (math.isnan(x) and math.isnan(y)) or x == y
    return x == y


def _map_eq(x, y):
    return set(x) == set(y) and all(_eq(x[k], y[k]) for k in x)


def sim_equal(a, b):
    """Bit-for-bit SimResult equality (NaN-aware float maps)."""
    assert a.durations_ms == b.durations_ms
    assert _map_eq(a.time_per_1000_iters_s, b.time_per_1000_iters_s)
    assert _map_eq(a.link_utilization, b.link_utilization)
    assert _eq(a.avg_bw_utilization, b.avg_bw_utilization)
    assert a.readjustments == b.readjustments
    assert _map_eq(a.finish_times_ms, b.finish_times_ms)
    assert _eq(a.total_completion_ms, b.total_completion_ms)
    assert a.iterations_done == b.iterations_done
    assert a.reconfigurations == b.reconfigurations


def small_cluster(n=2, bw=25.0):
    nodes = [Node(f"n{i}", Resources(cpu=32, mem=256, gpu=4), bw_gbps=bw)
             for i in range(n)]
    return Cluster(nodes)


def wl(job):
    return Workload(name=job.name, jobs=[job])


def _scheduled(jobs):
    """Place ``jobs`` on a fresh 2-node cluster (real comm flows need task
    placements); returns (cluster, registry)."""
    cl = small_cluster()
    fw = SchedulingFramework(cl, MetronomePlugin())
    for j in jobs:
        assert fw.schedule_workload(wl(j))
    return cl, fw.registry


def _both_loops(jobs_factory, cfg, prepare=None, **sim_kwargs):
    """Run the same scheduled setup through both loops; ``prepare`` sees
    each simulator before it runs."""
    out = []
    for loop in ("array", "legacy"):
        jobs = jobs_factory()
        cl, registry = _scheduled(jobs)
        sim = ClusterSimulator(
            cl, jobs, dataclasses.replace(cfg, event_loop=loop),
            registry=registry,
            **{k: (v() if callable(v) else v) for k, v in sim_kwargs.items()})
        if prepare is not None:
            prepare(sim)
        out.append((sim, sim.run()))
    return out


# ---------------------------------------------------------------------------
# oracle parity: array loop bit-for-bit against the retained legacy loop
# ---------------------------------------------------------------------------

class TestOracleParity:
    @pytest.mark.parametrize("sid", PINNED)
    def test_static_snapshots(self, sid):
        scen = snapshot_scenario(sid, n_iterations=30)
        arr = run(scen, Policy("metronome"), CFG)
        leg = run(scen, Policy("metronome"), LEGACY)
        sim_equal(arr.sim, leg.sim)
        assert arr.accepted == leg.accepted
        assert arr.placements == leg.placements

    @pytest.mark.parametrize("sid", DYNAMIC_SNAPSHOTS)
    def test_dynamic_snapshots(self, sid):
        scen = dynamic_scenario(sid, n_iterations=30)
        arr = run(scen, Policy("metronome"), CFG)
        leg = run(scen, Policy("metronome"), LEGACY)
        sim_equal(arr.sim, leg.sim)
        assert arr.accepted == leg.accepted

    def test_online_trace_with_departures(self):
        """Arrivals + departures through the full online path: both loops
        admit, run, and truncate identically."""
        from repro.core.harness import run_trace_experiment
        from repro.core.trace import (generate_trace, trace_departure_events,
                                      trace_to_jobs)
        trace = generate_trace(
            MODEL_FLEET, duration_s=600, total_gpus=13, target_load=0.8,
            seed=2, job_duration_range_s=(60, 120))[:6]
        evs = trace_departure_events(trace, time_scale=1.0)
        results = []
        for loop in ("array", "legacy"):
            cluster, _, _ = make_snapshot("S1")
            jobs = trace_to_jobs(trace, MODEL_FLEET, time_scale=1.0,
                                 open_ended=True)
            wls = [Workload(name=j.name, jobs=[j]) for j in jobs]
            for w in wls:
                for j in w.jobs:
                    j.workload = w.name
                    for t in j.tasks:
                        t.workload = w.name
            cfg = SimConfig(duration_ms=900_000, seed=0, jitter_std=0.01,
                            event_loop=loop)
            results.append(run_trace_experiment(
                "metronome", cluster, wls, cfg, events=list(evs)))
        sim_equal(results[0].sim, results[1].sim)
        assert results[0].rejected == results[1].rejected

    def test_unknown_event_loop_rejected(self):
        with pytest.raises(ValueError, match="unknown event_loop"):
            ClusterSimulator(small_cluster(), [],
                             SimConfig(event_loop="turbo"))


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

class TestEdgeCases:
    CFG = SimConfig(duration_ms=10_000.0, seed=0, jitter_std=0.0)

    def _job(self, name="j", **kw):
        kw.setdefault("n_tasks", 2)
        kw.setdefault("period_ms", 100)
        kw.setdefault("duty", 0.4)
        kw.setdefault("bw_gbps", 20.0)
        kw.setdefault("n_iterations", 5)
        return make_job(name, **kw)

    def test_starved_flow_never_finishes(self):
        """Background traffic claims a link's full capacity: the flow rate
        is zero, no finish event ever fires, and the loop still terminates
        at the duration cap (in both loops, identically)."""
        evs = [BackgroundFlowChange(50.0, link="n0", rate_gbps=25.0)]
        (sa, ra), (sl, rl) = _both_loops(
            lambda: [self._job()], self.CFG, events=lambda: list(evs))
        sim_equal(ra, rl)
        for sim, res in ((sa, ra), (sl, rl)):
            st = sim.jobs["j"]
            assert st.phase == COMM  # stuck mid-comm at the cap
            assert math.isnan(res.finish_times_ms["j"])
            assert res.iterations_done["j"] == 0
            assert sim.now == pytest.approx(self.CFG.duration_ms)

    def test_multiple_events_share_one_timestamp(self):
        """All events due at one tick drain together, in stream order."""
        evs = [BackgroundFlowChange(5_000.0, link="n0", rate_gbps=10.0),
               LinkCapacityChange(5_000.0, link="n1", allocatable_gbps=12.0),
               TrafficChange(5_000.0, job="j", duty_mult=1.5)]
        (sa, ra), (sl, rl) = _both_loops(
            lambda: [self._job(n_iterations=40)], self.CFG,
            events=lambda: list(evs))
        sim_equal(ra, rl)
        for sim in (sa, sl):
            assert sim.cluster.node("n0").allocatable_gbps == pytest.approx(15.0)
            assert sim.cluster.node("n1").allocatable_gbps == pytest.approx(12.0)
            # duty 0.4 * 1.5 -> comm 60ms of the 100ms period
            assert sim.jobs["j"].job.traffic.duty == pytest.approx(0.6)

    def test_arrival_tied_with_event_time(self):
        """An online arrival at exactly an event's timestamp: the event
        applies and the job is admitted in the same tick, identically in
        both loops."""
        def arrivals():
            late = self._job("late", submit_time_s=5.0)
            return [wl(late)]

        results = []
        for loop in ("array", "legacy"):
            cl = small_cluster()
            fw = SchedulingFramework(cl, MetronomePlugin())
            early = self._job("early", n_iterations=80)
            assert fw.schedule_workload(wl(early))
            sim = ClusterSimulator(
                cl, [early], dataclasses.replace(self.CFG, event_loop=loop),
                registry=fw.registry, framework=fw, arrivals=arrivals(),
                events=[BackgroundFlowChange(5_000.0, link="n0",
                                             rate_gbps=5.0)])
            results.append((sim, sim.run()))
        (sa, ra), (sl, rl) = results
        sim_equal(ra, rl)
        for sim, res in results:
            assert "late" in sim.jobs
            assert res.iterations_done["late"] > 0
            assert sim.cluster.node("n0").allocatable_gbps == pytest.approx(20.0)


# ---------------------------------------------------------------------------
# unknown-target warnings (once per offender)
# ---------------------------------------------------------------------------

class TestUnknownTargetWarnings:
    CFG = SimConfig(duration_ms=3_000.0, seed=0, jitter_std=0.0)

    def _run(self, events):
        sim = ClusterSimulator(
            small_cluster(),
            [make_job("j", n_tasks=2, period_ms=100, duty=0.3,
                      bw_gbps=10.0, n_iterations=5)],
            self.CFG, events=events)
        return sim

    def test_unknown_bg_link_warns_once(self):
        evs = [BackgroundFlowChange(100.0, link="ghost", rate_gbps=5.0),
               BackgroundFlowChange(200.0, link="ghost", rate_gbps=9.0)]
        with pytest.warns(UnknownEventTargetWarning) as rec:
            self._run(evs).run()
        ours = [w for w in rec if isinstance(w.message,
                                             UnknownEventTargetWarning)]
        assert len(ours) == 1  # once per offender, not per event
        assert ours[0].message.kind == "link"
        assert ours[0].message.name == "ghost"
        assert ours[0].message.time_ms == pytest.approx(100.0)

    def test_unknown_traffic_job_warns_once(self):
        evs = [TrafficChange(100.0, job="nobody", duty_mult=2.0),
               TrafficChange(200.0, job="nobody", duty_mult=0.5)]
        with pytest.warns(UnknownEventTargetWarning) as rec:
            self._run(evs).run()
        ours = [w for w in rec if isinstance(w.message,
                                             UnknownEventTargetWarning)]
        assert len(ours) == 1
        assert ours[0].message.kind == "job"
        assert ours[0].message.name == "nobody"

    def test_unknown_capacity_link_warns(self):
        evs = [LinkCapacityChange(100.0, link="uplink:nowhere",
                                  allocatable_gbps=1.0)]
        with pytest.warns(UnknownEventTargetWarning):
            self._run(evs).run()

    def test_distinct_offenders_warn_separately(self):
        evs = [BackgroundFlowChange(100.0, link="ghost-a", rate_gbps=5.0),
               BackgroundFlowChange(150.0, link="ghost-b", rate_gbps=5.0)]
        with pytest.warns(UnknownEventTargetWarning) as rec:
            self._run(evs).run()
        names = sorted(w.message.name for w in rec
                       if isinstance(w.message, UnknownEventTargetWarning))
        assert names == ["ghost-a", "ghost-b"]

    def test_known_targets_do_not_warn(self):
        import warnings as warnings_mod
        evs = [BackgroundFlowChange(100.0, link="n0", rate_gbps=5.0),
               TrafficChange(200.0, job="j", duty_mult=1.2)]
        with warnings_mod.catch_warnings():
            warnings_mod.simplefilter("error", UnknownEventTargetWarning)
            self._run(evs).run()  # must not raise


# ---------------------------------------------------------------------------
# SimConfig.profile
# ---------------------------------------------------------------------------

class TestProfile:
    def _cfg(self, loop):
        return SimConfig(duration_ms=10_000.0, seed=0, jitter_std=0.0,
                         event_loop=loop, profile=True)

    def _jobs(self):
        return [make_job("a", n_tasks=2, period_ms=100, duty=0.4,
                         bw_gbps=20.0, n_iterations=40),
                make_job("b", n_tasks=2, period_ms=130, duty=0.3,
                         bw_gbps=10.0, n_iterations=40, submit_time_s=0.013)]

    @pytest.mark.parametrize("loop", ["array", "legacy"])
    def test_profile_populated(self, loop):
        jobs = self._jobs()
        cl, registry = _scheduled(jobs)
        sim = ClusterSimulator(cl, jobs, self._cfg(loop), registry=registry)
        res = sim.run()
        p = res.profile
        assert p is not None and p.loop == loop
        assert p.ticks > 0 and p.steps > 0 and p.solves > 0
        phases = p.phase_seconds()
        assert set(phases) == {"assign", "next_event", "advance", "events",
                               "step"}
        assert all(v >= 0.0 for v in phases.values())
        assert p.as_dict()["ticks"] == p.ticks

    def test_array_loop_skips_clean_assigns(self):
        """Dirty-link tracking: ticks where no flow/capacity changed skip
        the rate solve entirely.  The single-task job's flowless phase
        timers fire inside the others' comm windows — pure-timer ticks
        that leave every link clean."""
        jobs = self._jobs() + [
            make_job("c", n_tasks=1, period_ms=17, duty=0.3, bw_gbps=1.0,
                     n_iterations=400)]
        cl, registry = _scheduled(jobs)
        sim = ClusterSimulator(cl, jobs, self._cfg("array"),
                               registry=registry)
        p = sim.run().profile
        assert p.skipped_assigns > 0
        assert p.solves + p.skipped_assigns <= p.ticks

    def test_profile_off_by_default(self):
        sim = ClusterSimulator(small_cluster(), self._jobs(),
                               SimConfig(duration_ms=2_000.0))
        assert sim.run().profile is None

    def _online(self, profile=True, duration_ms=30_000.0):
        """Online arrivals on the testbed under the default scheduler, with
        the vectorized fill: admissions, dirty components and fills."""
        from repro.core.experiment import build_scheduler
        from repro.core.trace import (generate_trace, trace_departure_events,
                                      trace_to_jobs)
        trace = generate_trace(
            MODEL_FLEET, duration_s=600, total_gpus=13, target_load=0.9,
            seed=2, job_duration_range_s=(60, 120))[:6]
        cluster, _, _ = make_snapshot("S1")
        wls = []
        for j in trace_to_jobs(trace, MODEL_FLEET, time_scale=1.0,
                               open_ended=True):
            j.workload = j.name
            for t in j.tasks:
                t.workload = j.name
            wls.append(Workload(name=j.name, jobs=[j]))
        plugin, controller = build_scheduler(Policy("default"))
        fw = SchedulingFramework(cluster, plugin)
        cfg = SimConfig(duration_ms=duration_ms, seed=0, jitter_std=0.0,
                        fluid_backend="jnp", profile=profile)
        return ClusterSimulator(
            cluster, [], cfg, controller=controller, registry=fw.registry,
            framework=fw, arrivals=wls,
            events=trace_departure_events(trace, time_scale=1.0))

    def test_profile_splits_assign_and_admission(self):
        """The parts of ``assign`` and of the rate solve are set and nest
        inside their parents; admission is booked where arrivals come."""
        sim = self._online()
        p = sim.run().profile
        st = sim.fluid.stats
        assert p.components_s > 0.0 and p.problems_s > 0.0
        assert 0 < p.dirty_components <= p.components
        assert p.components_s + p.problems_s + st.batch_s <= p.assign_s
        assert st.misses > 0
        assert min(st.key_s, st.pack_s, st.device_s) > 0.0
        assert st.key_s + st.pack_s + st.device_s <= st.batch_s
        assert 0.0 < p.admit_s <= p.events_s + p.step_s

    def test_profile_off_keeps_new_timers_at_zero(self):
        sim = self._online(profile=False)
        assert sim.run().profile is None
        st = sim.fluid.stats
        assert st.misses > 0  # the solve ran
        assert (st.batch_s, st.key_s, st.pack_s, st.device_s) == (0.0,) * 4

    def test_no_annotation_without_a_profiler_session(self, monkeypatch):
        """Without a profiler session no span is constructed; with one
        (``is_enabled`` forced true) the same counter sees them."""
        import jax.profiler

        made = []

        class Counting(jax.profiler.TraceAnnotation):
            def __init__(self, name, **kw):
                made.append(name)
                super().__init__(name, **kw)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        self._online(duration_ms=10_000.0).run()
        assert made == []
        monkeypatch.setattr(Counting, "is_enabled",
                            staticmethod(lambda: True))
        self._online(duration_ms=10_000.0).run()
        assert {"sim.assign", "sim.admit", "fluid.components",
                "fluid.solve_batch"} <= set(made)

    def test_spans_on_the_profiler_clock(self, tmp_path):
        """Under a CPU profiler session the host plane holds one
        ``sim.assign`` span per tick and every ``fluid.*`` span inside
        one; admission spans lie inside the events or step phase."""
        import bisect

        import jax

        sim = self._online(duration_ms=10_000.0)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            res = sim.run()
        finally:
            jax.profiler.stop_trace()
        path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
        data = jax.profiler.ProfileData.from_file(str(path))
        spans = {}
        for plane in data.planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("sim.", "fluid.")):
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))

        def inside(child, parents):
            parents = sorted(parents)
            starts = [s for s, _ in parents]
            for s, e in child:
                k = bisect.bisect_right(starts, s) - 1
                assert k >= 0 and e <= parents[k][1], (s, e)

        assert len(spans["sim.assign"]) == res.profile.ticks
        fluid_names = {n for n in spans if n.startswith("fluid.")}
        assert {"fluid.components", "fluid.problems", "fluid.solve_batch",
                "fluid.key", "fluid.pack", "fluid.device"} <= fluid_names
        for name in fluid_names:
            inside(spans[name], spans["sim.assign"])
        for name in ("fluid.key", "fluid.pack", "fluid.device"):
            inside(spans[name], spans["fluid.solve_batch"])
        inside(spans["sim.admit"], spans["sim.events"] + spans["sim.step"])


# ---------------------------------------------------------------------------
# whole-tick rate memo (vectorized backends)
# ---------------------------------------------------------------------------

class TestRateMemo:
    TOL = 5e-3  # the fill parity tolerance (TestSolveBatch, test_fluid)

    def _cfg(self, backend="jnp", duration_ms=6_000.0):
        return SimConfig(duration_ms=duration_ms, seed=0, jitter_std=0.0,
                         fluid_backend=backend, profile=True)

    def _jobs(self):
        # two periodic jobs on the same two host links
        return [make_job("a", n_tasks=2, period_ms=100, duty=0.4,
                         bw_gbps=20.0, n_iterations=60),
                make_job("b", n_tasks=2, period_ms=130, duty=0.3,
                         bw_gbps=10.0, n_iterations=60)]

    def _sim(self, backend="jnp", duration_ms=6_000.0, **kw):
        jobs = self._jobs()
        cl, registry = _scheduled(jobs)
        return ClusterSimulator(cl, jobs, self._cfg(backend, duration_ms),
                                registry=registry, **kw)

    def _spy(self, sim):
        """Record every rate solve: (time, hit, ordered content ids, the
        fill problem of the active flows, the rates written)."""
        solves = []
        inner = sim._assign_rates_array
        prof = sim.profile

        def spied():
            before = (prof.rate_memo_hits, prof.rate_memo_misses)
            inner()
            after = (prof.rate_memo_hits, prof.rate_memo_misses)
            if after == before:
                return
            tbl = sim._flows
            act = sim._active_slots()
            cap_of = sim._allocatable()
            paths = [tbl.paths[s] for s in act]
            caps = {l: cap_of(l) for p in paths for l in p}
            solves.append((sim.now, after[0] > before[0],
                           tbl.cid[act].tobytes(),
                           (tbl.demand[act].copy(), paths, caps),
                           tbl.rate[act].copy()))

        sim._assign_rates_array = spied
        return solves

    def test_recurring_mix_hits(self):
        p = self._sim().run().profile
        assert p.rate_memo_hits > 0
        assert p.rate_memo_hits > p.rate_memo_misses

    def test_hits_and_misses_count_every_solve(self):
        p = self._sim().run().profile
        assert p.solves > 0
        assert p.rate_memo_hits + p.rate_memo_misses == p.solves
        # the component path runs on misses only
        assert 0 < p.dirty_components <= p.components

    def test_capacity_change_forces_miss(self):
        """A recurring mix after a capacity change misses once per mix on
        the new caps, and its rates are the oracle's on the new caps."""
        t_ev = 3_000.0
        sim = self._sim(events=[LinkCapacityChange(t_ev, link="n0",
                                                   capacity_gbps=12.0)])
        solves = self._spy(sim)
        sim.run()
        before = [s for s in solves if s[0] < t_ev]
        after = [s for s in solves if s[0] >= t_ev]
        assert before and after
        seen_before = {cid for _, _, cid, _, _ in before}
        assert any(hit for _, hit, _, _, _ in before)
        first_after = {}
        for _, hit, cid, _, _ in after:
            first_after.setdefault(cid, hit)
        # every mix misses the first time it runs on the new caps, those
        # that recur from before the change included; later runs hit
        assert not any(first_after.values())
        assert set(first_after) & seen_before
        assert sum(not hit for _, hit, _, _, _ in after) == len(first_after)
        for _, _, _, (d, paths, caps), rates in after:
            assert caps["n0"] == 12.0
            gold = fluid.fill_python(d, paths, caps)
            np.testing.assert_allclose(rates, gold, atol=self.TOL, rtol=0)

    def test_content_ids_intern_demand_and_path(self):
        from repro.core.simulator import _FlowTable
        tbl = _FlowTable({"n0": 0, "n1": 1}, cap=2)
        a, b = tbl.reserve(0, [10.0, 12.0], [("n0",), ("n0",)])
        c, d = tbl.reserve(1, [10.0, 10.0], [("n1",), ("n0",)])  # grows
        assert tbl.cap == 4
        assert len({tbl.cid[a], tbl.cid[b], tbl.cid[c]}) == 3
        assert tbl.cid[d] == tbl.cid[a]

    def test_memo_clears_at_memo_max(self):
        sim = self._sim()
        sim.fluid.memo_max = 2
        sizes = []
        inner = sim._assign_rates_array

        def spied():
            inner()
            sizes.append(len(sim._rate_memo))

        sim._assign_rates_array = spied
        p = sim.run().profile
        assert p.rate_memo_misses > 2
        assert max(sizes) == 2
        # a miss into a full memo clears it before storing
        assert 1 in sizes[sizes.index(2):]

    def test_iteration_times_match_python_oracle(self):
        jnp_res = self._sim("jnp").run()
        py_res = self._sim("python").run()
        assert jnp_res.profile.rate_memo_hits > 0
        assert py_res.profile.rate_memo_hits == 0
        assert py_res.profile.rate_memo_misses == 0
        assert jnp_res.iterations_done == py_res.iterations_done
        for job, want in py_res.durations_ms.items():
            np.testing.assert_allclose(jnp_res.durations_ms[job], want,
                                       rtol=self.TOL)


# ---------------------------------------------------------------------------
# job-owned flow slots and the membership cache of the active set
# ---------------------------------------------------------------------------

def _uncached(sim):
    """Empty the membership cache before every tick, so each change of
    the active set rebuilds it."""
    inner = sim._assign_rates_array

    def cleared():
        sim._active_cache.clear()
        inner()

    sim._assign_rates_array = cleared


def _record_rates(sim):
    """Every tick's time, ordered active slots and the rates in them."""
    ticks = []
    inner = sim._assign_rates_array

    def spied():
        inner()
        act = sim._active_slots()
        ticks.append((sim.now, act.tolist(), sim._flows.rate[act].tobytes()))

    sim._assign_rates_array = spied
    return ticks


def _record_reservations(sim):
    """(time, job, slots, cached active sets left) of every reservation."""
    got = []
    inner = sim._reserve_slots

    def spied(st, specs):
        own = inner(st, specs)
        got.append((sim.now, st.name, own.slots.tolist(),
                    len(sim._active_cache)))
        return own

    sim._reserve_slots = spied
    return got


def _check_membership(sim):
    """Before every tick: the membership key names exactly the alive
    slots with volume left, the cached active set in force is that set in
    (job, pos) order, and a job shows its slots to the harness
    (``flow_slots``) during a comm phase with flows and only then."""
    inner = sim._assign_rates_array
    checked = []

    def spied():
        tbl = sim._flows
        want = np.nonzero(tbl.alive & (tbl.remaining > EPS))[0]
        mask = sim._active_bits
        assert {s for s in range(tbl.cap) if mask >> s & 1} == set(want)
        act = sim._active_slots()
        assert sorted(act.tolist()) == sorted(want.tolist())
        keys = list(zip(tbl.job[act].tolist(), tbl.pos[act].tolist()))
        assert keys == sorted(keys)
        for st in sim._jobs_list:
            with_flows = st.phase == COMM and bool(sim._jhasflows[st.index])
            assert (st.flow_slots is not None) == with_flows
            if st.flow_slots is not None:
                assert st.flow_slots is st.owned.slots
        checked.append(sim.now)
        inner()

    sim._assign_rates_array = spied
    return checked


class TestMembershipCache:
    def _jobs(self):
        return [make_job("a", n_tasks=2, period_ms=100, duty=0.4,
                         bw_gbps=20.0, n_iterations=60),
                make_job("b", n_tasks=2, period_ms=130, duty=0.3,
                         bw_gbps=10.0, n_iterations=60)]

    def _sim(self, backend, prepare=()):
        jobs = self._jobs()
        cl, registry = _scheduled(jobs)
        sim = ClusterSimulator(
            cl, jobs, SimConfig(duration_ms=6_000.0, seed=0, jitter_std=0.0,
                                fluid_backend=backend, profile=True),
            registry=registry)
        for fn in prepare:
            fn(sim)
        return sim

    @pytest.mark.parametrize("backend", ["python", "jnp"])
    def test_recurring_mix_hits_bitwise_as_uncached(self, backend):
        """A recurring flow mix is answered by the membership cache, and
        every tick's rates and the delivered bytes are bitwise those of
        the same run rebuilding the active set on every change."""
        cached = self._sim(backend)
        ticks = _record_rates(cached)
        res = cached.run()
        rebuilt = self._sim(backend, prepare=[_uncached])
        ticks_rebuilt = _record_rates(rebuilt)
        res_rebuilt = rebuilt.run()
        p, q = res.profile, res_rebuilt.profile
        assert p.active_hits > p.active_misses > 0
        assert q.active_hits == 0
        assert q.active_misses == p.active_hits + p.active_misses
        assert ticks == ticks_rebuilt
        assert cached._delivered_vec.tobytes() == \
            rebuilt._delivered_vec.tobytes()
        sim_equal(res, res_rebuilt)

    def test_slots_owned_across_iterations(self):
        """Each job reserves its slots once and keeps them through every
        later iteration; its content ids never change."""
        sim = self._sim("jnp")
        got = _record_reservations(sim)
        res = sim.run()
        assert sorted(name for _, name, _, _ in got) == ["a", "b"]
        assert min(res.iterations_done.values()) > 10

    def test_departure_reuses_slots_and_clears_cache(self):
        """A job departs, a later arrival takes its released slots: the
        reservation writes their content and empties the cache, and the
        python backend stays bit-for-bit with the legacy loop."""
        cfg = SimConfig(duration_ms=8_000.0, seed=0, jitter_std=0.0)
        runs = []
        for loop in ("array", "legacy"):
            cl = small_cluster()
            fw = SchedulingFramework(cl, MetronomePlugin())
            jobs = [make_job("early", n_tasks=2, period_ms=100, duty=0.4,
                             bw_gbps=20.0, n_iterations=200),
                    make_job("stay", n_tasks=2, period_ms=130, duty=0.3,
                             bw_gbps=10.0, n_iterations=200)]
            for j in jobs:
                assert fw.schedule_workload(wl(j))
            late = make_job("late", n_tasks=2, period_ms=90, duty=0.5,
                            bw_gbps=15.0, n_iterations=200,
                            submit_time_s=3.0)
            sim = ClusterSimulator(
                cl, jobs, dataclasses.replace(cfg, event_loop=loop),
                registry=fw.registry, framework=fw, arrivals=[wl(late)],
                events=[JobDeparture(2_000.0, job="early")])
            got = _record_reservations(sim) if loop == "array" else None
            checked = _check_membership(sim) if loop == "array" else None
            runs.append((sim, sim.run(), got, checked))
        (sa, ra, got, checked), (sl, rl, _, _) = runs
        sim_equal(ra, rl)
        assert checked
        first = {name: (t, slots, left) for t, name, slots, left
                 in reversed(got)}
        assert set(first) == {"early", "stay", "late"}
        assert first["late"][0] >= 3_000.0
        assert set(first["late"][1]) & set(first["early"][1])
        assert first["late"][2] == 0  # the cache was emptied
        assert sa.jobs["early"].owned is None
        assert ra.iterations_done["late"] > 0

    def test_host_failure_releases_and_rereserves(self):
        """A host failure stalls the jobs on it and releases their slots;
        on recovery they reserve anew, emptying the cache, and the python
        backend stays bit-for-bit with the legacy loop."""
        cfg = SimConfig(duration_ms=6_000.0, seed=0, jitter_std=0.0)
        evs = [HostFailure(2_000.0, host="n0"),
               HostRecovery(2_500.0, host="n0")]
        seen = {}

        def prepare(sim):
            if sim._array_mode:
                seen["got"] = _record_reservations(sim)
                seen["checked"] = _check_membership(sim)

        (sa, ra), (sl, rl) = _both_loops(self._jobs, cfg, prepare=prepare,
                                         events=lambda: list(evs))
        sim_equal(ra, rl)
        got = seen["got"]
        assert seen["checked"]
        for name in ("a", "b"):
            times = [t for t, n, _, _ in got if n == name]
            assert len(times) == 2 and times[1] >= 2_500.0
        assert all(left == 0 for t, _, _, left in got if t >= 2_500.0)
        assert min(ra.iterations_done.values()) > 0

    def test_changed_specs_reserve_anew(self):
        """When a job's flow specs change between comm phases it releases
        its slots and reserves new ones with the new content, in both
        loops alike."""
        cfg = SimConfig(duration_ms=4_000.0, seed=0, jitter_std=0.0)
        seen = {}

        def prepare(sim):
            inner = sim._flow_specs

            def specs(job):
                out = inner(job)
                if sim.now < 2_000.0 or job.name != "a":
                    return out
                return [dataclasses.replace(fs, demand_gbps=fs.demand_gbps / 2)
                        for fs in out]

            sim._flow_specs = specs
            if sim._array_mode:
                seen["got"] = _record_reservations(sim)

        (sa, ra), (sl, rl) = _both_loops(self._jobs, cfg, prepare=prepare)
        sim_equal(ra, rl)
        assert [n for _, n, _, _ in seen["got"]].count("a") == 2
        own = sa.jobs["a"].owned
        assert sa._flows.demand[own.slots].tolist() == [10.0, 10.0]

    def test_reuse_under_the_same_key_rebuilds(self):
        """A job's slots go to another job and come back active before the
        next lookup, so the key is the one in force: the set is rebuilt
        with the new owner's order and content ids."""
        sim = self._sim("jnp")
        a, b = sim.jobs["a"], sim.jobs["b"]
        assert sim._start_comm_flows(a, 50.0)
        before = sim._active_slots().copy()
        key, cids_before = sim._active_bits, sim._act_cids
        sim._clear_flows(a)
        sim._release_slots(a)
        assert sim._start_comm_flows(b, 50.0)
        assert sim._active_bits == key
        assert b.owned.slots.tolist() == before[::-1].tolist()
        act = sim._active_slots()
        want = sim._build_active()
        assert act.tolist() == want[0].tolist() == b.owned.slots.tolist()
        assert sim._act_cids == want[4] != cids_before
        assert sim.profile.active_misses == 2

    def test_cache_bounded_by_memo_max(self):
        sim = self._sim("jnp")
        sim.fluid.memo_max = 2
        sizes = []
        inner = sim._assign_rates_array

        def spied():
            inner()
            sizes.append(len(sim._active_cache))

        sim._assign_rates_array = spied
        p = sim.run().profile
        assert p.active_misses > 2
        assert max(sizes) == 2

    def test_membership_key_on_online_trace(self):
        """Arrivals, departures and the vectorized fill: the key and the
        cached set agree with a rebuild on every tick."""
        sim = TestProfile()._online(duration_ms=30_000.0)
        checked = _check_membership(sim)
        p = sim.run().profile
        assert len(checked) == p.ticks
        assert p.active_hits > 0 and p.active_misses > 0

    def test_flow_table_reserve_release_reuse(self):
        from repro.core.simulator import _FlowTable
        tbl = _FlowTable({"n0": 0, "n1": 1, "up": 2}, cap=4)
        a = tbl.reserve(0, [10.0, 12.0], [("n0",), ("n1", "up")])
        b = tbl.reserve(1, [5.0], [("n1",)])
        assert tbl.job[a].tolist() == [0, 0] and tbl.pos[a].tolist() == [0, 1]
        assert not tbl.alive[np.r_[a, b]].any()
        assert tbl.links[a[1]].tolist() == [1, 2]
        cid_a0 = tbl.cid[a[0]]
        tbl.free(a)
        assert tbl.job[a].tolist() == [-1, -1]
        assert tbl.paths[a[0]] is None and tbl.paths[a[1]] is None
        c = tbl.reserve(2, [12.0, 7.0, 10.0, 3.0],
                        [("n0", "up", "n1"), ("n0",), ("n0",), ("n1",)])
        assert set(a.tolist()) <= set(c.tolist())  # released slots reused
        assert tbl.cap == 8  # the fifth slot in use grew the table
        assert tbl.maxp == 3  # a longer path widened the link rows
        assert tbl.job[c].tolist() == [2, 2, 2, 2]
        assert tbl.pos[c].tolist() == [0, 1, 2, 3]
        assert tbl.demand[c].tolist() == [12.0, 7.0, 10.0, 3.0]
        assert tbl.links[c[0]].tolist() == [0, 2, 1]
        assert tbl.links[c[1]].tolist() == [0, -1, -1]
        assert tbl.links[b[0]].tolist() == [1, -1, -1]
        assert tbl.cid[c[2]] == cid_a0  # (10.0, ("n0",)) again
        assert len({tbl.cid[s] for s in c}) == 4


# ---------------------------------------------------------------------------
# batched multi-problem solves + shape-bucketed corpus batching
# ---------------------------------------------------------------------------

def _random_problems(rng, n, fabric=True):
    probs = []
    for _ in range(n):
        n_hosts = int(rng.integers(2, 7))
        n_flows = int(rng.integers(1, 13))
        demands = rng.uniform(0.2, 30.0, size=n_flows)
        caps = {f"h{k}": float(rng.uniform(1.0, 40.0))
                for k in range(n_hosts)}
        paths = []
        for _ in range(n_flows):
            h = int(rng.integers(n_hosts))
            path = [f"h{h}"]
            if fabric and rng.random() < 0.5:
                path.append(f"uplink:{h % 2}")
            paths.append(tuple(path))
        if fabric:
            caps["uplink:0"] = float(rng.uniform(2.0, 25.0))
            caps["uplink:1"] = float(rng.uniform(2.0, 25.0))
        probs.append((demands, paths, caps))
    return probs


class TestSolveBatch:
    TOL = 5e-3

    def test_python_matches_sequential_oracle(self):
        probs = _random_problems(np.random.default_rng(11), 8)
        eng = fluid.FluidEngine("python")
        for got, (d, p, c) in zip(eng.solve_batch(probs), probs):
            np.testing.assert_array_equal(
                got, fluid.fill_python(np.asarray(d, dtype=float), p, c))

    def test_jnp_batch_within_tolerance(self):
        probs = _random_problems(np.random.default_rng(12), 8)
        eng = fluid.FluidEngine("jnp")
        for got, (d, p, c) in zip(eng.solve_batch(probs), probs):
            gold = fluid.fill_python(np.asarray(d, dtype=float), p, c)
            np.testing.assert_allclose(got, gold, atol=self.TOL, rtol=0)
        # shape-bucketed dispatch recorded its occupancy
        cs = eng.corpus_stats
        assert cs.calls >= 1 and cs.problems == 8
        assert 0.0 < cs.flow_occupancy <= 1.0
        assert 0.0 < cs.link_occupancy <= 1.0

    def test_incremental_memo_hits(self):
        probs = _random_problems(np.random.default_rng(13), 5)
        eng = fluid.FluidEngine("python", incremental=True)
        first = eng.solve_batch(probs)
        assert eng.stats.misses == 5
        second = eng.solve_batch(probs)
        assert eng.stats.hits == 5
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)

    def test_sampling_for_error_audit(self):
        """sample_stride captures (problem, solution) pairs so benches can
        re-solve them against the oracle for a max-abs-err figure."""
        probs = _random_problems(np.random.default_rng(14), 6)
        eng = fluid.FluidEngine("python")
        eng.sample_stride = 2
        eng.solve_batch(probs)
        assert len(eng.samples) == 3
        d, p, c, rates = eng.samples[0]
        np.testing.assert_array_equal(
            rates, fluid.fill_python(np.asarray(d, dtype=float), p, c))


class TestCorpusBucketing:
    def test_bucketed_matches_unbucketed(self):
        probs = _random_problems(np.random.default_rng(15), 12)
        mats = [fluid.problem_matrix(*p)[:3] for p in probs]
        plain = fluid.fill_corpus(mats, backend="jnp")
        stats = fluid.CorpusStats()
        bucketed = fluid.fill_corpus(mats, backend="jnp",
                                     bucket_shapes=True, stats=stats)
        for a, b in zip(plain, bucketed):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
        assert stats.problems == 12
        assert stats.buckets >= 1  # batched dispatches happened
        # padding is visible, never silent: dispatched >= real slot counts
        assert stats.flow_slots >= stats.flow_used > 0
        assert stats.link_slots >= stats.link_used > 0

    def test_round_pow2(self):
        assert fluid._round_pow2(1) == 4
        assert fluid._round_pow2(4) == 4
        assert fluid._round_pow2(5) == 8
        assert fluid._round_pow2(17) == 32

    def test_stats_as_dict(self):
        stats = fluid.CorpusStats()
        d = stats.as_dict()
        assert d["calls"] == 0
        assert d["flow_occupancy"] == 1.0  # no dispatch -> no waste
