"""Incremental scheduling hot path (ISSUE 5): epoch-tagged LinkView,
memoized joint planning, one-shot batched candidate scoring.

Four pillars:

  * epoch soundness — every mutation of the demand view (reserve/unreserve,
    dynamic events, capacity/background changes) advances the
    (cluster, registry) epoch, so :class:`repro.core.rotation.PlanCache`
    can never serve a stale result (D1/D2 event streams pinned);
  * memo bit-for-bit — Score with the planner memo enabled equals the
    unmemoized path exactly on every golden snapshot (S1-S5/F2/F4/J1):
    placements, global offsets and per-link shifts;
  * batched joint solving — joint_solve_batch (numpy and the stacked
    (C, L, R, S) kernel dispatch) equals per-problem joint_solve;
  * the timing-artifact schema (BENCH_sched_time.json) round-trips.
"""

import numpy as np
import pytest

from repro.configs.metronome_testbed import (
    dynamic_scenario, make_dynamic_snapshot, make_snapshot, snapshot_scenario)
from repro.core import rotation, scoring, geometry
from repro.core.cluster import Cluster, Node, Resources
from repro.core.contention import LinkView
from repro.core.controller import StopAndWaitController
from repro.core.events import (BackgroundFlowChange, LinkCapacityChange,
                               TrafficChange)
from repro.core.experiment import Policy, run, sweep
from repro.core.framework import SchedulingFramework
from repro.core.results import to_timing_dict, validate_timing_dict
from repro.core.scheduler import MetronomePlugin
from repro.core.simulator import ClusterSimulator, SimConfig
from repro.core.workload import Workload, make_job

GOLDEN_SIDS = ("S1", "S2", "S3", "S4", "S5", "F2", "F4", "J1")


def schedule_snapshot(sid, memo=True):
    cluster, wls, bg = make_snapshot(sid, n_iterations=50)
    ctrl = StopAndWaitController()
    plugin = MetronomePlugin(controller=ctrl, memo=memo)
    fw = SchedulingFramework(cluster, plugin)
    for wl in wls:
        fw.schedule_workload(wl)
    return cluster, fw, ctrl, plugin


# ---------------------------------------------------------------------------
# Epoch tagging and invalidation
# ---------------------------------------------------------------------------

class TestEpochs:
    def _small(self):
        nodes = [Node(f"n{i}", Resources(cpu=64, mem=512, gpu=8),
                      bw_gbps=25.0)
                 for i in range(2)]
        return Cluster(nodes)

    def test_schedule_and_evict_bump_epochs(self):
        cluster = self._small()
        fw = SchedulingFramework(cluster, MetronomePlugin())
        job = make_job("j", n_tasks=2, period_ms=100.0, duty=0.3,
                       bw_gbps=5.0)
        e0 = (cluster.epoch, fw.registry.epoch)
        assert fw.schedule_workload(Workload(name="w", jobs=[job]))
        e1 = (cluster.epoch, fw.registry.epoch)
        assert e1 != e0
        fw.evict_job(job)
        assert (cluster.epoch, fw.registry.epoch) != e1

    def test_view_epoch_capture(self):
        cluster = self._small()
        fw = SchedulingFramework(cluster, MetronomePlugin())
        view = LinkView.from_registry(cluster, fw.registry)
        assert view.epoch == (cluster.epoch, fw.registry.epoch)
        # a raw view (simulator-style) carries no epoch: caches disabled
        assert LinkView(cluster).epoch is None

    @pytest.mark.parametrize("event", [
        LinkCapacityChange(0.0, link="n0", allocatable_gbps=10.0),
        BackgroundFlowChange(0.0, link="n0", rate_gbps=8.0),
        BackgroundFlowChange(0.0, link="n0", rate_gbps=8.0,
                             adjust_allocatable=False),
    ])
    def test_events_bump_cluster_epoch(self, event):
        cluster = self._small()
        fw = SchedulingFramework(cluster, MetronomePlugin())
        sim = ClusterSimulator(cluster, [], SimConfig(duration_ms=1.0),
                               registry=fw.registry)
        before = cluster.epoch
        sim._apply_event(event)
        assert cluster.epoch > before

    def test_traffic_change_bumps_registry_epoch(self):
        cluster = self._small()
        ctrl = StopAndWaitController()
        fw = SchedulingFramework(cluster, MetronomePlugin(controller=ctrl))
        job = make_job("j", n_tasks=2, period_ms=100.0, duty=0.3,
                       bw_gbps=5.0)
        fw.schedule_workload(Workload(name="w", jobs=[job]))
        sim = ClusterSimulator(cluster, [job], SimConfig(duration_ms=1.0),
                               controller=ctrl, registry=fw.registry)
        before = fw.registry.epoch
        sim._apply_event(TrafficChange(0.0, job="j", duty_mult=1.5))
        assert fw.registry.epoch > before

    def test_plan_cache_epoch_scoping(self):
        cache = rotation.PlanCache()
        cache.put((1, 1), "k", "v")
        assert cache.get((1, 1), "k") == "v"
        # ANY epoch advance clears the store: stale reuse is impossible
        assert cache.get((1, 2), "k") is None
        assert cache.get((1, 1), "k") is None  # even going "back"
        # epoch-less views bypass the cache entirely
        cache.put(None, "k", "v")
        assert cache.get(None, "k") is None

    def test_capacity_event_invalidates_scheduler_cache(self):
        """After a LinkCapacityChange the plugin's warmed cache entries are
        unreachable: the epoch moved, so the next Score re-solves against
        the new allocatable bandwidth."""
        cluster = self._small()
        ctrl = StopAndWaitController()
        plugin = MetronomePlugin(controller=ctrl)
        fw = SchedulingFramework(cluster, plugin)
        for i in range(2):
            j = make_job(f"j{i}", n_tasks=2, period_ms=100.0, duty=0.4,
                         bw_gbps=15.0)
            fw.schedule_workload(Workload(name=j.name, jobs=[j]))
        view = LinkView.from_registry(cluster, fw.registry)
        score0, scheme0 = rotation.solve_link(
            view, fw.registry, "n0", cache=plugin.plan_cache)
        assert plugin.plan_cache._store  # warmed
        sim = ClusterSimulator(cluster, [], SimConfig(duration_ms=1.0),
                               controller=ctrl, registry=fw.registry)
        sim._apply_event(LinkCapacityChange(0.0, link="n0",
                                            allocatable_gbps=12.0))
        fresh = LinkView.from_registry(cluster, fw.registry)
        assert fresh.epoch != view.epoch
        assert plugin.plan_cache.get(fresh.epoch, "anything") is None

    def test_cached_scheme_is_mutation_safe(self):
        """Consumers mutate LinkSchemes in place (controller eviction);
        cached copies must stay pristine."""
        cluster = self._small()
        fw = SchedulingFramework(cluster, MetronomePlugin())
        for i in range(2):
            j = make_job(f"j{i}", n_tasks=2, period_ms=100.0, duty=0.4,
                         bw_gbps=15.0)
            fw.schedule_workload(Workload(name=j.name, jobs=[j]))
        cache = rotation.PlanCache()
        view = LinkView.from_registry(cluster, fw.registry)
        _s, first = rotation.solve_link(view, fw.registry, "n0", cache=cache)
        first.jobs.pop()
        first.shifts_slots += 99
        _s, again = rotation.solve_link(view, fw.registry, "n0", cache=cache)
        assert cache.hits >= 1
        assert len(again.jobs) == len(first.jobs) + 1
        assert not np.array_equal(again.shifts_slots, first.shifts_slots)


# ---------------------------------------------------------------------------
# Memoized Score is bit-for-bit the unmemoized Score (goldens)
# ---------------------------------------------------------------------------

class TestMemoBitForBit:
    @pytest.mark.parametrize("sid", GOLDEN_SIDS)
    def test_schedule_identical(self, sid):
        _, fw_m, ctrl_m, plugin_m = schedule_snapshot(sid, memo=True)
        _, fw_n, ctrl_n, _ = schedule_snapshot(sid, memo=False)
        place_m = {uid: t.node for uid, t in fw_m.registry.tasks.items()}
        place_n = {uid: t.node for uid, t in fw_n.registry.tasks.items()}
        assert place_m == place_n
        assert ctrl_m.global_offsets_ms == ctrl_n.global_offsets_ms
        assert set(ctrl_m.links) == set(ctrl_n.links)
        for lid in ctrl_m.links:
            a, b = ctrl_m.links[lid].scheme, ctrl_n.links[lid].scheme
            assert a.jobs == b.jobs
            assert np.array_equal(a.shifts_slots, b.shifts_slots)
            assert a.base_ms == b.base_ms
            assert a.score == b.score
        # the memo actually fired somewhere across the goldens
        if sid in ("S1", "S2", "F2", "F4", "J1"):
            assert plugin_m.plan_cache.hits + plugin_m.plan_cache.misses > 0

    @pytest.mark.parametrize("sid", ("D1", "D2"))
    def test_dynamic_event_stream_identical(self, sid):
        """Full D1/D2 runs (capacity + background fluctuation mid-run) with
        the memo on equal the unmemoized run exactly — if the epoch ever
        failed to advance, a stale scheme would change the realignments and
        the measured durations."""
        results = []
        for memo in (True, False):
            cluster, wls, bg, events = make_dynamic_snapshot(
                sid, n_iterations=60)
            ctrl = StopAndWaitController()
            plugin = MetronomePlugin(controller=ctrl, memo=memo)
            fw = SchedulingFramework(cluster, plugin)
            jobs = []
            for wl in wls:
                assert fw.schedule_workload(wl)
                jobs.extend(wl.jobs)
            ctrl.run_offline_recalculation(fw.registry, cluster)
            sim = ClusterSimulator(
                cluster, jobs, SimConfig(duration_ms=60_000.0, seed=3),
                controller=ctrl, background=bg, registry=fw.registry,
                events=events)
            res = sim.run()
            results.append((res.durations_ms, res.finish_times_ms,
                            res.readjustments, res.reconfigurations,
                            dict(ctrl.global_offsets_ms)))
        assert results[0] == results[1]


# ---------------------------------------------------------------------------
# Batched joint solving == per-problem joint solving
# ---------------------------------------------------------------------------

class TestJointBatch:
    def _j1_specs(self):
        cluster, wls, bg = make_snapshot("J1", n_iterations=50)
        ctrl = StopAndWaitController()
        fw = SchedulingFramework(cluster, MetronomePlugin(controller=ctrl))
        for wl in wls:
            fw.schedule_workload(wl)
        view = LinkView.from_registry(cluster, fw.registry)
        links = [l for l in view.planning_links()
                 if rotation.solve_link(view, fw.registry, l)[1] is not None]
        return view, fw.registry, links

    def test_batch_equals_individual(self):
        view, registry, links = self._j1_specs()
        single = rotation.joint_solve(view, registry, links)
        batch = rotation.joint_solve_batch(
            [(view, links), (view, links)], registry)
        assert len(batch) == 2
        for jr in batch:
            assert jr is not None
            assert jr.jobs == single.jobs
            assert np.array_equal(jr.shifts, single.shifts)
            assert jr.score == single.score
            assert jr.offsets_ms == single.offsets_ms

    def test_batch_warms_cache(self):
        view, registry, links = self._j1_specs()
        cache = rotation.PlanCache()
        rotation.joint_solve_batch([(view, links)], registry, cache=cache)
        hits_before = cache.hits
        again = rotation.joint_solve(view, registry, links, cache=cache)
        assert cache.hits == hits_before + 1
        single = rotation.joint_solve(view, registry, links)
        assert np.array_equal(again.shifts, single.shifts)

    def test_cache_key_includes_solver_selection(self):
        """max_exhaustive selects exhaustive vs coordinate descent, which
        produce different shifts — a cached exhaustive result must never be
        served to a coordinate-descent request under the same epoch."""
        view, registry, links = self._j1_specs()
        cache = rotation.PlanCache()
        rotation.joint_solve(view, registry, links, cache=cache)
        cd_cached = rotation.joint_solve(view, registry, links, cache=cache,
                                         max_exhaustive=0)
        cd_fresh = rotation.joint_solve(view, registry, links,
                                        max_exhaustive=0)
        assert np.array_equal(cd_cached.shifts, cd_fresh.shifts)

    def test_batch_kernel_backend_matches_numpy(self, interpret_kernels):
        view, registry, links = self._j1_specs()
        ran = interpret_kernels[("score_multilink", "interpret")]
        res_np = rotation.joint_solve_batch(
            [(view, links)], registry, backend="numpy")[0]
        res_k = rotation.joint_solve_batch(
            [(view, links)], registry, backend="kernel")[0]
        assert interpret_kernels[("score_multilink", "interpret")] == ran + 1
        assert np.array_equal(res_np.shifts, res_k.shifts)
        assert res_np.score == pytest.approx(res_k.score, abs=1e-4)


# ---------------------------------------------------------------------------
# Candidate-batched multi-link kernel parity
# ---------------------------------------------------------------------------

class TestBatchKernelParity:
    def _problem(self, seed=0, c=3, l=3):
        rng = np.random.default_rng(seed)
        pats = geometry.pattern_matrix([1, 1, 2], [0.3, 0.25, 0.2], 72)
        banks = scoring.rolled_bank(pats, [1, 24, 36])
        bw = rng.uniform(5.0, 20.0, size=(c, l, 3))
        caps = rng.uniform(18.0, 30.0, size=(c, l))
        base = bw[:, :, 0:1] * pats[0][None, None, :]
        bank_a = bw[:, :, 1, None, None] * banks[1][None, None]
        bank_b = bw[:, :, 2, None, None] * banks[2][None, None]
        return base, bank_a, bank_b, caps

    def test_batch_ref_matches_per_candidate_ref(self):
        from repro.kernels import ref
        base, bank_a, bank_b, caps = self._problem()
        want = np.asarray(ref.metronome_score_multilink_batch_ref(
            base, bank_a, bank_b, caps))
        for ci in range(base.shape[0]):
            per = np.asarray(ref.metronome_score_multilink_ref(
                base[ci], bank_a[ci], bank_b[ci], caps[ci]))
            assert np.allclose(want[ci], per, atol=1e-5)

    def test_interpret_kernel_matches_ref(self):
        from repro.kernels import ops as kops
        from repro.kernels import ref
        base, bank_a, bank_b, caps = self._problem(seed=1)
        got = kops.score_multilink_batch(base, bank_a, bank_b, caps,
                                         interpret=True)
        want = np.asarray(ref.metronome_score_multilink_batch_ref(
            base, bank_a, bank_b, caps))
        assert got.shape == (3, 24, 36)
        assert np.allclose(got, want, atol=1e-4)

    def test_zero_demand_padding_links_are_neutral(self):
        from repro.kernels import ref
        base, bank_a, bank_b, caps = self._problem(seed=2, l=2)
        pad = lambda x: np.concatenate(  # noqa: E731
            [x, np.zeros_like(x[:, :1])], axis=1)
        caps_pad = np.concatenate(
            [caps, np.ones_like(caps[:, :1])], axis=1)
        want = np.asarray(ref.metronome_score_multilink_batch_ref(
            base, bank_a, bank_b, caps))
        got = np.asarray(ref.metronome_score_multilink_batch_ref(
            pad(base), pad(bank_a), pad(bank_b), caps_pad))
        assert np.allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# Parallel sweep == serial sweep
# ---------------------------------------------------------------------------

class TestParallelSweep:
    CFG = SimConfig(duration_ms=8_000.0, seed=3, jitter_std=0.01)

    def test_workers_identical_to_serial(self):
        scenarios = [snapshot_scenario("S2", n_iterations=20),
                     dynamic_scenario("D1", n_iterations=20)]
        policies = [Policy(scheduler="metronome"),
                    Policy(scheduler="default")]
        serial = sweep(scenarios, policies, self.CFG)
        threaded = sweep(scenarios, policies, self.CFG, workers=3)
        assert serial.to_json_dict() == threaded.to_json_dict()
        # row-major cell order preserved
        order = [(c.scenario, c.policy) for c in threaded.cells]
        assert order == [(s.name, p.name) for s in scenarios
                         for p in policies]

    def test_workers_preserve_error_isolation(self):
        from repro.core.experiment import Scenario

        def boom():
            raise RuntimeError("boom")

        scenarios = [Scenario(name="bad", build=boom),
                     snapshot_scenario("S2", n_iterations=10)]
        policies = [Policy(scheduler="default")]
        res = sweep(scenarios, policies, self.CFG, workers=2)
        assert [c.status for c in res.cells] == ["error", "ok"]
        assert "boom" in res.cells[0].error


# ---------------------------------------------------------------------------
# Timing artifact schema
# ---------------------------------------------------------------------------

class TestTimingArtifact:
    def test_roundtrip_valid(self):
        rows = [{"name": "fig16_sched_metronome_2jobs",
                 "us_per_call": 6400.0, "derived": "ms_per_pod=3.20",
                 "origin": "sched_time"}]
        doc = to_timing_dict(rows, smoke=True)
        assert validate_timing_dict(doc) == []
        assert doc["kind"] == "timing" and doc["smoke"] is True

    def test_validation_catches_drift(self):
        doc = to_timing_dict(
            [{"name": "x", "us_per_call": 1.0, "derived": "", "origin": ""}])
        assert validate_timing_dict({}) != []
        bad = dict(doc)
        bad["rows"] = [{"name": "", "us_per_call": "nope"}]
        problems = validate_timing_dict(bad)
        assert any("name" in p for p in problems)
        assert any("us_per_call" in p for p in problems)
        assert any("derived" in p for p in problems)

    def test_emit_rows_recorded(self):
        import benchmarks.common as common
        before = len(common.RECORDED_EMITS)
        old_origin = common.CURRENT_ORIGIN
        common.CURRENT_ORIGIN = "unit-test"
        try:
            common.emit("unit_row", 12.5, "k=v")
        finally:
            common.CURRENT_ORIGIN = old_origin
        row = common.RECORDED_EMITS[-1]
        assert len(common.RECORDED_EMITS) == before + 1
        assert row == {"name": "unit_row", "us_per_call": 12.5,
                       "derived": "k=v", "origin": "unit-test"}
        doc = to_timing_dict([row])
        assert validate_timing_dict(doc) == []
        common.RECORDED_EMITS.pop()
