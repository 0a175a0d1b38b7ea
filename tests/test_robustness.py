"""Imperfect-information control plane (DESIGN.md section 19).

Four subsystems under test:

  * the telemetry channel model — ``TelemetryView`` sampling semantics
    (sample-and-hold, staleness, noise, dropout carry), its determinism
    contract (pure function of (link, sample-slot), never query order),
    and the oracle-identity guarantee: a transparent channel is
    bit-for-bit the no-channel path;
  * fault injection — link/host failure+recovery events, each run pinned
    bit-for-bit to ``tests/goldens/sim_goldens.json`` (see
    ``tests/goldens/record.py``), including same-timestamp stacks,
    zero-capacity links, and flapping trains;
  * graceful-degradation control — the controller's hysteresis gate and
    measured-vs-declared demand reconciliation;
  * event-stream boundary validation — ``strict_events`` raising a
    structured error, default mode warn-once-dropping bad values while
    unknown targets keep the historical fire-time warning.
"""
import dataclasses
import math

import pytest

from repro.configs.metronome_testbed import (FAULT_SNAPSHOTS,
                                             dynamic_scenario, fault_scenario,
                                             make_snapshot)
from repro.core.cluster import Cluster, Node, Resources
from repro.core.controller import StopAndWaitController
from repro.core.events import (BackgroundFlowChange, EventValidationError,
                               HostFailure, HostRecovery, LinkCapacityChange,
                               LinkFailure, LinkRecovery, TrafficChange,
                               UnknownEventTargetWarning, flapping_schedule,
                               validate_stream)
from repro.core.experiment import Policy, Scenario, run
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin
from repro.core.simulator import COMM, STALLED, ClusterSimulator, SimConfig
from repro.core.telemetry import TelemetryChannel, TelemetryView
from repro.core.workload import Workload, make_job
from test_event_loop import pinned, sim_equal

CFG = SimConfig(duration_ms=20_000.0, seed=3, jitter_std=0.01)


def small_cluster(n=2, bw=25.0):
    nodes = [Node(f"n{i}", Resources(cpu=32, mem=256, gpu=4), bw_gbps=bw)
             for i in range(n)]
    return Cluster(nodes)


def wl(job):
    return Workload(name=job.name, jobs=[job])


def _job(name="j", **kw):
    kw.setdefault("n_tasks", 2)
    kw.setdefault("period_ms", 100)
    kw.setdefault("duty", 0.4)
    kw.setdefault("bw_gbps", 20.0)
    kw.setdefault("n_iterations", 50)
    return make_job(name, **kw)


def _scheduled(jobs, controller=None):
    cl = small_cluster()
    fw = SchedulingFramework(cl, MetronomePlugin(controller=controller))
    for j in jobs:
        assert fw.schedule_workload(wl(j))
    return cl, fw.registry


def _one_run(jobs, cfg, **sim_kwargs):
    cl, registry = _scheduled(jobs)
    sim = ClusterSimulator(cl, jobs, cfg, registry=registry, **sim_kwargs)
    return sim, sim.run()


# ---------------------------------------------------------------------------
# the runs pinned by value (tests/goldens/sim_goldens.json)
# ---------------------------------------------------------------------------

FAULT_CFG = SimConfig(duration_ms=10_000.0, seed=0, jitter_std=0.0)


def _faulted(*evs, jobs=lambda: [_job()]):
    """A run function: ``jobs()`` scheduled on the 2-node cluster and run
    under ``FAULT_CFG`` with the events ``evs``."""
    return lambda: _one_run(jobs(), FAULT_CFG, events=list(evs))


def _one_job_per_node():
    # each job demands a node's full GPU capacity, pinning one per node
    return [_job(name, n_tasks=1, bw_gbps=5.0, n_iterations=500,
                 resources=Resources(cpu=4, mem=16, gpu=4))
            for name in ("a", "b")]


def _noisy_channel():
    chan = TelemetryChannel(sample_period_ms=500.0, noise_std=0.15,
                            staleness_ms=250.0, dropout=0.1)
    return run(dynamic_scenario("D1", n_iterations=30), Policy("metronome"),
               dataclasses.replace(CFG, telemetry=chan))


def _fault_snapshot(sid):
    return lambda: run(
        fault_scenario(sid, n_iterations=30, start_ms=3_000.0,
                       period_ms=6_000.0, down_ms=1_000.0, n_cycles=2),
        Policy("metronome"), CFG)


def _silent_drift():
    return _one_run(
        [_job()], SimConfig(duration_ms=10_000.0, seed=0, jitter_std=0.0),
        events=[TrafficChange(1_000.0, job="j", duty_mult=1.5,
                              declared=False)])


GOLDEN_RUNS = {
    "telemetry.noisy_channel": _noisy_channel,
    "fault.clean": _faulted(),
    "link.failure_recovery": _faulted(LinkFailure(2_000.0, link="n0"),
                                      LinkRecovery(4_000.0, link="n0")),
    "link.degraded_recovery": _faulted(
        LinkFailure(1_000.0, link="n0"),
        LinkRecovery(2_000.0, link="n0", capacity_gbps=10.0)),
    "link.zero_capacity": _faulted(LinkFailure(500.0, link="n0")),
    "link.same_timestamp_stack": _faulted(LinkFailure(2_000.0, link="n0"),
                                          LinkRecovery(2_000.0, link="n0")),
    "link.double_failure": _faulted(
        LinkFailure(1_000.0, link="n0"), LinkFailure(1_500.0, link="n0"),
        LinkRecovery(2_000.0, link="n0"),
        LinkRecovery(2_500.0, link="n0")),  # not failed: no-op
    "link.unknown": _faulted(LinkFailure(100.0, link="ghost")),
    "host.stall_recovery": _faulted(HostFailure(2_000.0, host="n0"),
                                    HostRecovery(5_000.0, host="n0")),
    "host.unrecovered": _faulted(HostFailure(2_000.0, host="n0"),
                                 jobs=lambda: [_job(n_iterations=500)]),
    "host.same_timestamp_flap": _faulted(HostFailure(2_000.0, host="n0"),
                                         HostRecovery(2_000.0, host="n0")),
    "host.other_host": _faulted(HostFailure(2_000.0, host="n1"),
                                jobs=_one_job_per_node),
    **{f"fault.{sid}": _fault_snapshot(sid) for sid in FAULT_SNAPSHOTS},
    "reconcile.silent_drift": _silent_drift,
}


# ---------------------------------------------------------------------------
# telemetry channel model
# ---------------------------------------------------------------------------

class TestTelemetryChannel:
    def test_defaults_are_benign(self):
        ch = TelemetryChannel()
        assert ch.noise_std == 0.0 and ch.dropout == 0.0

    @pytest.mark.parametrize("kw", [
        dict(sample_period_ms=math.nan),
        dict(noise_std=-0.1), dict(noise_std=math.inf),
        dict(staleness_ms=-1.0), dict(staleness_ms=math.nan),
        dict(dropout=-0.1), dict(dropout=1.0),
    ])
    def test_invalid_params_rejected(self, kw):
        with pytest.raises(ValueError):
            TelemetryChannel(**kw)


class TestTelemetryView:
    def test_sample_and_hold(self):
        cl = small_cluster()
        tv = TelemetryView(cl, TelemetryChannel(sample_period_ms=1000.0),
                           seed=1)
        tv.now_ms = 600.0
        assert tv.link_alloc("n0") == 25.0
        cl.node("n0").allocatable_gbps = 10.0
        tv.record_change(700.0, ["n0"])
        tv.now_ms = 900.0  # still sample slot 0: the change is invisible
        assert tv.link_alloc("n0") == 25.0
        tv.now_ms = 1100.0  # slot 1 samples the truth in force at t=1000
        assert tv.link_alloc("n0") == 10.0

    def test_staleness_pins_older_sample(self):
        cl = small_cluster()
        tv = TelemetryView(
            cl, TelemetryChannel(sample_period_ms=1000.0, staleness_ms=1500.0),
            seed=1)
        cl.node("n0").allocatable_gbps = 10.0
        tv.record_change(700.0, ["n0"])
        tv.now_ms = 1100.0  # t - staleness < 0 -> slot 0 -> pre-change truth
        assert tv.link_alloc("n0") == 25.0
        tv.now_ms = 2600.0  # slot 1 -> truth at t=1000 -> post-change
        assert tv.link_alloc("n0") == 10.0

    def test_continuous_mode_staleness_only(self):
        cl = small_cluster()
        tv = TelemetryView(
            cl, TelemetryChannel(sample_period_ms=0.0, staleness_ms=500.0),
            seed=1)
        cl.node("n0").allocatable_gbps = 10.0
        tv.record_change(700.0, ["n0"])
        tv.now_ms = 1000.0  # sees truth at 500 (pre-change)
        assert tv.link_alloc("n0") == 25.0
        tv.now_ms = 1300.0  # sees truth at 800 (post-change)
        assert tv.link_alloc("n0") == 10.0

    def test_noise_is_seed_deterministic(self):
        ch = TelemetryChannel(sample_period_ms=100.0, noise_std=0.2)

        def observe(seed):
            tv = TelemetryView(small_cluster(), ch, seed=seed)
            out = []
            for k in range(10):
                tv.now_ms = k * 100.0 + 50.0
                out.append(tv.link_alloc("n0"))
            return out

        assert observe(7) == observe(7)
        assert observe(7) != observe(8)
        assert all(v >= 0.0 for v in observe(7))

    def test_dropout_carry_is_query_order_independent(self):
        ch = TelemetryChannel(sample_period_ms=100.0, noise_std=0.1,
                              dropout=0.5)

        def observe(slots):
            tv = TelemetryView(small_cluster(), ch, seed=5)
            out = {}
            for k in slots:
                tv.now_ms = k * 100.0 + 50.0
                out[k] = tv.link_alloc("n0")
            return out

        fwd = observe(range(10))
        rev = observe(list(reversed(range(10))))
        assert fwd == rev

    def test_dropout_carries_previous_sample(self):
        # dropout ~1 => every sample after slot 0 is lost; the slot-0
        # observation is carried forever (sample 0 is never dropped)
        ch = TelemetryChannel(sample_period_ms=100.0, noise_std=0.3,
                              dropout=0.999999)
        cl = small_cluster()
        tv = TelemetryView(cl, ch, seed=5)
        tv.now_ms = 50.0
        first = tv.link_alloc("n0")
        cl.node("n0").allocatable_gbps = 1.0
        tv.record_change(60.0, ["n0"])
        tv.now_ms = 950.0
        assert tv.link_alloc("n0") == first

    def test_unknown_link_raises_like_cluster(self):
        tv = TelemetryView(small_cluster(), TelemetryChannel(), seed=1)
        with pytest.raises(KeyError, match="ghost"):
            tv.link_alloc("ghost")

    def test_delegation_and_truthful_capacity(self):
        cl = small_cluster()
        tv = TelemetryView(
            cl, TelemetryChannel(sample_period_ms=100.0, noise_std=0.5),
            seed=1)
        assert tv.link_capacity("n0") == cl.link_capacity("n0")
        assert tv.node_names == cl.node_names
        tv.bump_epoch()
        assert cl.epoch == 1  # mutations hit the real cluster

    def test_fluctuation_tracks_noise(self):
        ch_noisy = TelemetryChannel(sample_period_ms=100.0, noise_std=0.3)
        ch_clean = TelemetryChannel(sample_period_ms=100.0)

        def fluct(ch):
            tv = TelemetryView(small_cluster(), ch, seed=5)
            for k in range(30):
                tv.now_ms = k * 100.0 + 50.0
                tv.link_alloc("n0")
            return tv.fluctuation("n0")

        assert fluct(ch_noisy) > 0.0
        assert fluct(ch_clean) == 0.0
        tv = TelemetryView(small_cluster(), ch_noisy, seed=5)
        assert tv.fluctuation("n0") == 0.0  # no samples yet


class TestOracleIdentity:
    """A transparent channel must be BIT-FOR-BIT the no-channel path,
    under the float64 oracle and a vectorized fill alike, and a noisy
    channel must see what the pinned run saw."""

    @pytest.mark.parametrize("backend", ["python", "jnp"])
    @pytest.mark.parametrize("channel", [
        TelemetryChannel(sample_period_ms=0.0),   # continuous, undistorted
        TelemetryChannel(sample_period_ms=1000.0),  # sampled, undistorted
    ])
    def test_transparent_channel_is_oracle(self, backend, channel):
        scen = dynamic_scenario("D1", n_iterations=30)
        policy = Policy("metronome", sim_backend=backend)
        base = run(scen, policy, CFG)
        tel = run(scen, policy, dataclasses.replace(CFG, telemetry=channel))
        sim_equal(base.sim, tel.sim)
        assert base.placements == tel.placements

    def test_noisy_channel_loop_parity(self):
        """Telemetry is a pure function of (link, sample slot), never of
        query order, so the run sees the channel the pinned run saw."""
        pinned(GOLDEN_RUNS, "telemetry.noisy_channel")

    def test_noisy_run_is_seed_deterministic(self):
        scen = dynamic_scenario("D1", n_iterations=30)
        chan = TelemetryChannel(sample_period_ms=500.0, noise_std=0.2,
                                dropout=0.05)
        cfg = dataclasses.replace(CFG, telemetry=chan)
        a = run(scen, Policy("metronome"), cfg)
        b = run(scen, Policy("metronome"), cfg)
        sim_equal(a.sim, b.sim)


# ---------------------------------------------------------------------------
# fault injection: link/host failure + recovery, pinned bit-for-bit
# ---------------------------------------------------------------------------

class TestLinkFailure:
    def test_failure_zeroes_recovery_restores(self):
        sim, res = pinned(GOLDEN_RUNS, "link.failure_recovery")
        n0 = sim.cluster.node("n0")
        assert n0.bw_gbps == 25.0 and n0.allocatable_gbps is None
        assert res.iterations_done["j"] > 0
        # ~2s of the 10s window was dead: the job finishes later
        _, clean = pinned(GOLDEN_RUNS, "fault.clean")
        assert res.finish_times_ms["j"] > clean.finish_times_ms["j"] + 1_000.0

    def test_degraded_recovery(self):
        sim, _ = pinned(GOLDEN_RUNS, "link.degraded_recovery")
        assert sim.cluster.node("n0").bw_gbps == 10.0

    def test_zero_capacity_link_stalls_flows(self):
        """While a traversed link is failed, comm flows have rate 0: the
        job sits mid-comm with no finish event until recovery."""
        sim, res = pinned(GOLDEN_RUNS, "link.zero_capacity")
        assert sim.jobs["j"].phase == COMM  # stuck mid-comm at the cap
        assert math.isnan(res.finish_times_ms["j"])

    def test_same_timestamp_failure_recovery_stack(self):
        """A failure and its recovery at ONE timestamp cancel exactly:
        the run is bit-for-bit an event-free run."""
        _, res = pinned(GOLDEN_RUNS, "link.same_timestamp_stack")
        _, clean = pinned(GOLDEN_RUNS, "fault.clean")
        sim_equal(res, clean)

    def test_double_failure_single_recovery(self):
        """Failing a failed link is a no-op; the first recovery restores
        the ORIGINAL pre-failure capacity (flap-overlap semantics)."""
        sim, _ = pinned(GOLDEN_RUNS, "link.double_failure")
        n0 = sim.cluster.node("n0")
        assert n0.bw_gbps == 25.0 and n0.allocatable_gbps is None

    def test_unknown_link_warns(self):
        with pytest.warns(UnknownEventTargetWarning):
            pinned(GOLDEN_RUNS, "link.unknown")


class TestHostFailure:
    def test_stall_and_recovery(self):
        sim, res = pinned(GOLDEN_RUNS, "host.stall_recovery")
        _, clean = pinned(GOLDEN_RUNS, "fault.clean")
        st = sim.jobs["j"]
        assert st.phase != STALLED  # recovered
        assert not st.stall_hosts
        assert res.iterations_done["j"] > 0
        # the ~3s stall pushes the finish well past the clean run's
        assert res.finish_times_ms["j"] > clean.finish_times_ms["j"] + 2_000.0

    def test_unrecovered_host_stalls_to_cap(self):
        sim, res = pinned(GOLDEN_RUNS, "host.unrecovered")
        assert sim.jobs["j"].phase == STALLED
        assert math.isnan(res.finish_times_ms["j"])
        # iterations froze at the failure: ~2s worth of 100ms periods
        assert res.iterations_done["j"] <= 21

    def test_same_timestamp_host_flap_costs_one_iteration(self):
        """Failure and recovery at ONE timestamp: the host is back
        instantly, but the in-flight iteration was abandoned by the
        failure and restarts from its top — host flaps are destructive
        by design (unlike link flaps, which only gate rates)."""
        sim, res = pinned(GOLDEN_RUNS, "host.same_timestamp_flap")
        _, clean = pinned(GOLDEN_RUNS, "fault.clean")
        assert res.iterations_done["j"] == clean.iterations_done["j"]
        assert res.finish_times_ms["j"] == pytest.approx(
            clean.finish_times_ms["j"] + 100.0)  # one redone period
        assert sim.jobs["j"].phase != STALLED
        assert not sim._failed_hosts and not sim._failed_links

    def test_job_on_other_host_unaffected(self):
        """A job with no task on the failed host keeps running."""
        sim, _ = pinned(GOLDEN_RUNS, "host.other_host")
        stalled = [n for n, st in sim.jobs.items() if st.phase == STALLED]
        running = [n for n, st in sim.jobs.items() if st.phase != STALLED]
        assert len(stalled) == 1 and len(running) == 1

    @pytest.mark.parametrize("sid", FAULT_SNAPSHOTS)
    def test_fault_snapshots_loop_parity(self, sid):
        pinned(GOLDEN_RUNS, f"fault.{sid}")


class TestFlappingSchedule:
    def test_alternating_train(self):
        evs = flapping_schedule("uplink:leaf0", start_ms=1_000.0,
                                period_ms=5_000.0, down_ms=500.0, n_cycles=3)
        assert len(evs) == 6
        assert [type(e).__name__ for e in evs[:2]] == ["LinkFailure",
                                                       "LinkRecovery"]
        assert evs[2].time_ms == 6_000.0 and evs[3].time_ms == 6_500.0

    def test_host_variant(self):
        evs = flapping_schedule("n0", start_ms=0.0, period_ms=100.0,
                                down_ms=10.0, n_cycles=1, host=True)
        assert isinstance(evs[0], HostFailure)
        assert isinstance(evs[1], HostRecovery)

    def test_down_must_fit_period(self):
        with pytest.raises(ValueError, match="down_ms"):
            flapping_schedule("n0", start_ms=0.0, period_ms=100.0,
                              down_ms=100.0, n_cycles=1)


# ---------------------------------------------------------------------------
# event-stream boundary validation
# ---------------------------------------------------------------------------

class TestEventValidation:
    CFG = SimConfig(duration_ms=3_000.0, seed=0, jitter_std=0.0)

    def _sim(self, events, **cfg_kw):
        cfg = dataclasses.replace(self.CFG, **cfg_kw)
        return ClusterSimulator(small_cluster(), [_job(n_iterations=5)],
                                cfg, events=events)

    BAD_VALUE_EVENTS = [
        TrafficChange(100.0, job="j", duty_mult=math.nan),
        TrafficChange(100.0, job="j", duty_mult=-1.0),
        BackgroundFlowChange(100.0, link="n0", rate_gbps=math.nan),
        LinkCapacityChange(100.0, link="n0", allocatable_gbps=-5.0),
        LinkCapacityChange(100.0, link="n0", capacity_gbps=math.inf),
        LinkRecovery(100.0, link="n0", capacity_gbps=-1.0),
        TrafficChange(math.nan, job="j", duty_mult=1.5),
        TrafficChange(-5.0, job="j", duty_mult=1.5),
    ]

    @pytest.mark.parametrize("ev", BAD_VALUE_EVENTS)
    def test_strict_raises_on_bad_values(self, ev):
        with pytest.raises(EventValidationError) as exc:
            self._sim([ev], strict_events=True).run()
        assert exc.value.problems[0].category == "bad-value"

    def test_strict_raises_on_unknown_targets(self):
        with pytest.raises(EventValidationError) as exc:
            self._sim([LinkFailure(100.0, link="ghost")],
                      strict_events=True).run()
        assert exc.value.problems[0].category == "unknown-target"

    def test_strict_reports_all_problems(self):
        evs = [TrafficChange(100.0, job="j", duty_mult=math.nan),
               HostFailure(200.0, host="ghost"),
               BackgroundFlowChange(300.0, link="n0", rate_gbps=math.inf)]
        with pytest.raises(EventValidationError) as exc:
            self._sim(evs, strict_events=True).run()
        assert len(exc.value.problems) == 3

    def test_default_drops_bad_values_with_one_warning(self):
        """Same malformed event twice: ONE warning, both dropped, and the
        run completes as if they were never submitted."""
        evs = [BackgroundFlowChange(100.0, link="n0", rate_gbps=math.nan),
               BackgroundFlowChange(200.0, link="n0", rate_gbps=math.nan)]
        with pytest.warns(UserWarning, match="dropped") as rec:
            sim = self._sim(evs)
            sim.run()
        dropped = [w for w in rec if "dropped" in str(w.message)]
        assert len(dropped) == 1
        assert sim.cluster.node("n0").allocatable_gbps is None

    def test_default_keeps_fire_time_unknown_warning(self):
        """Unknown targets are NOT dropped at the boundary: the historical
        fire-time warning (first offense time) is preserved."""
        evs = [BackgroundFlowChange(100.0, link="ghost", rate_gbps=5.0),
               BackgroundFlowChange(200.0, link="ghost", rate_gbps=9.0)]
        with pytest.warns(UnknownEventTargetWarning) as rec:
            self._sim(evs).run()
        ours = [w for w in rec
                if isinstance(w.message, UnknownEventTargetWarning)]
        assert len(ours) == 1
        assert ours[0].message.time_ms == pytest.approx(100.0)

    def test_validate_stream_clean(self):
        evs = [TrafficChange(100.0, job="j", duty_mult=1.5),
               LinkFailure(200.0, link="n0"),
               HostFailure(300.0, host="n1")]
        assert validate_stream(evs, known_links={"n0", "n1"},
                               known_hosts={"n0", "n1"},
                               known_jobs={"j"}) == []

    def test_strict_in_experiment_config(self):
        """strict_events rides SimConfig through the experiment API."""
        def build():
            cluster, wls, bg = make_snapshot("S2", n_iterations=10)
            return cluster, wls, bg, [TrafficChange(100.0, job="nobody",
                                                    duty_mult=2.0)]

        scen = Scenario(name="bad", build=build)
        cfg = dataclasses.replace(CFG, strict_events=True)
        with pytest.raises(EventValidationError):
            run(scen, Policy("metronome"), cfg)


# ---------------------------------------------------------------------------
# degradation control: hysteresis + reconciliation
# ---------------------------------------------------------------------------

class TestHysteresis:
    CFG = SimConfig(duration_ms=10_000.0, seed=0, jitter_std=0.0)

    def _run(self, events, **ctl_kw):
        controller = StopAndWaitController(**ctl_kw)
        jobs = [_job("a"), _job("b", period_ms=130, duty=0.3,
                                submit_time_s=0.001)]
        cl, registry = _scheduled(jobs, controller=controller)
        sim = ClusterSimulator(cl, jobs, self.CFG, controller=controller,
                               registry=registry, events=events)
        sim.run()
        return controller

    def test_min_interval_suppresses(self):
        evs = [BackgroundFlowChange(1_000.0, link="n0", rate_gbps=5.0),
               BackgroundFlowChange(2_000.0, link="n0", rate_gbps=10.0),
               BackgroundFlowChange(3_000.0, link="n0", rate_gbps=2.0)]
        loose = self._run(list(evs))
        tight = self._run(list(evs), hysteresis_ms=60_000.0)
        assert loose.suppressed_reconf_count == 0
        assert tight.suppressed_reconf_count == 2
        assert tight.reconf_count == 1
        assert tight.reconf_count < loose.reconf_count

    def test_magnitude_gate_suppresses_small_changes(self):
        evs = [BackgroundFlowChange(1_000.0, link="n0", rate_gbps=5.0),
               BackgroundFlowChange(2_000.0, link="n0", rate_gbps=5.2)]
        ctl = self._run(list(evs), hysteresis_frac=0.05)
        # 2nd change moves alloc by 0.2 of 25 (0.8%) < 5% of capacity
        assert ctl.suppressed_reconf_count == 1
        assert ctl.reconf_count == 1
        big = self._run(list(evs[:1]) + [
            BackgroundFlowChange(2_000.0, link="n0", rate_gbps=15.0)],
            hysteresis_frac=0.05)
        assert big.reconf_count == 2

    def test_dead_link_guard(self):
        """A failed (observed-dead) link never replans — there is no
        bandwidth to derive a rotation against; the recovery does."""
        evs = [LinkFailure(1_000.0, link="n0"),
               LinkRecovery(2_000.0, link="n0")]
        ctl = self._run(list(evs))
        assert ctl.reconf_count == 1  # recovery only

    def test_zero_hysteresis_is_seed_behavior(self):
        evs = [BackgroundFlowChange(1_000.0, link="n0", rate_gbps=5.0),
               BackgroundFlowChange(1_500.0, link="n0", rate_gbps=8.0)]
        ctl = self._run(list(evs))
        assert ctl.reconf_count == 2
        assert ctl.suppressed_reconf_count == 0


class TestReconciliation:
    def test_insufficient_evidence_returns_none(self):
        ctl = StopAndWaitController(reconcile=True, reconcile_window=4)
        for _ in range(3):
            assert ctl.reconcile_measurement("j", 80.0, 40.0) is None

    def test_median_deviation_triggers(self):
        ctl = StopAndWaitController(reconcile=True, reconcile_window=4,
                                    reconcile_frac=0.25)
        out = None
        for _ in range(4):
            out = ctl.reconcile_measurement("j", 80.0, 40.0)
        assert out == pytest.approx(80.0)
        assert ctl.reconcile_count == 1
        # evidence cleared after adoption: next report starts fresh
        assert ctl.reconcile_measurement("j", 80.0, 80.0) is None

    def test_within_tolerance_never_triggers(self):
        ctl = StopAndWaitController(reconcile=True, reconcile_window=4,
                                    reconcile_frac=0.25)
        for _ in range(10):
            assert ctl.reconcile_measurement("j", 44.0, 40.0) is None
        assert ctl.reconcile_count == 0

    def test_disabled_returns_none(self):
        ctl = StopAndWaitController()
        for _ in range(10):
            assert ctl.reconcile_measurement("j", 80.0, 40.0) is None

    def test_silent_drift_closed_by_reconciliation(self):
        """declared=False traffic drift: the profile stays stale unless
        the controller reconciles measured comm time against it."""
        def run_one(reconcile):
            controller = StopAndWaitController(reconcile=reconcile)
            jobs = [_job(n_iterations=200)]
            cl, registry = _scheduled(jobs, controller=controller)
            sim = ClusterSimulator(
                cl, jobs, SimConfig(duration_ms=15_000.0, seed=0,
                                    jitter_std=0.0),
                controller=controller, registry=registry,
                events=[TrafficChange(1_000.0, job="j", duty_mult=1.8,
                                      declared=False)])
            sim.run()
            return controller, sim

        stale_ctl, stale_sim = run_one(False)
        assert stale_ctl.reconcile_count == 0
        assert stale_sim.jobs["j"].job.traffic.duty == pytest.approx(0.4)
        assert stale_sim.jobs["j"].drift_mult == pytest.approx(1.8)

        rec_ctl, rec_sim = run_one(True)
        assert rec_ctl.reconcile_count >= 1
        # profile adopted the measurement: duty ~0.72 (0.4 * 1.8)
        assert rec_sim.jobs["j"].job.traffic.duty == pytest.approx(
            0.72, rel=0.1)
        # and the drift bookkeeping re-normalized toward 1
        assert rec_sim.jobs["j"].drift_mult == pytest.approx(1.0, rel=0.1)

    def test_silent_drift_loop_parity(self):
        pinned(GOLDEN_RUNS, "reconcile.silent_drift")
