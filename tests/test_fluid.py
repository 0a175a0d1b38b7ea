"""Backend parity of the fluid rate engine (``core/fluid.py``).

Three layers of evidence that the backend swap is safe:

  * property/seeded-random parity — random star and leaf–spine fill
    problems solved by the python oracle vs the vectorized jnp path vs the
    interpreted Pallas kernel must agree to float32 tolerance;
  * scenario-level parity — the pinned snapshots' actual fill problems
    (``LinkView.fill_problem``) through all three backends;
  * bit-for-bit goldens — ``Policy(sim_backend='python')`` must reproduce
    the default simulation EXACTLY on every pinned scenario (S1–S5, F2,
    F4, J1, D1, D2): the refactor moved the seed's per-flow loop, it must
    not have changed it.

Plus the machinery that rides along: incremental per-component memoization
(``FluidStats``), size-bucketed corpus batching (``fill_corpus``), the
production-trace generator, the ``Policy.sim_backend`` knob, process-mode
sweeps and the content-keyed sweep cache.
"""
import dataclasses
import json
import math
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.metronome_testbed import (DYNAMIC_SNAPSHOTS, MODEL_FLEET,
                                             dynamic_scenario, make_snapshot,
                                             snapshot_scenario)
from repro.core import fluid, rotation
from repro.core.contention import LinkView
from repro.core.controller import StopAndWaitController
from repro.core.experiment import Policy, run, sweep
from repro.core.framework import SchedulingFramework
from repro.core.scheduler import MetronomePlugin
from repro.core.simulator import SimConfig
from repro.core.trace import (active_jobs_at, generate_production_trace,
                              TraceJobSpec)

CFG = SimConfig(duration_ms=20_000.0, seed=3, jitter_std=0.01)
N_ITER = 30

# float32 fixed point with FILL_EPS termination: the vectorized backends
# track the float64 oracle to well under a Kbps on Gbps-scale rates
TOL = 5e-3

PINNED = ["S1", "S2", "S3", "S4", "S5", "F2", "F4", "J1"]


def scheduled(sid):
    """Schedule snapshot ``sid`` under Metronome; return (cluster, fw, wls)."""
    cluster, wls, _ = make_snapshot(sid, n_iterations=50)
    fw = SchedulingFramework(
        cluster, MetronomePlugin(controller=StopAndWaitController()))
    for wl in wls:
        assert fw.schedule_workload(wl)
    return cluster, fw, wls


def random_problem(rng, *, fabric):
    """One random fill problem: a star (every path one host link) or a
    2-leaf fabric (spanning flows add their leaf uplink to the path)."""
    n_hosts = int(rng.integers(2, 7))
    n_flows = int(rng.integers(1, 13))
    demands = rng.uniform(0.2, 30.0, size=n_flows)
    caps = {f"h{k}": float(rng.uniform(1.0, 40.0)) for k in range(n_hosts)}
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
    return demands, paths, caps


def solve_all_backends(demands, paths, caps):
    """(python, jnp, interpreted-kernel) rate vectors of one problem."""
    golden = fluid.fill_python(np.asarray(demands, dtype=float), paths, caps)
    mat = fluid.problem_matrix(demands, paths, caps)[:3]
    via_jnp = fluid.fill_many([mat], backend="jnp")[0]
    via_kernel = fluid.fill_many([mat], backend="kernel", interpret=True)[0]
    return golden, via_jnp, via_kernel


# ---------------------------------------------------------------------------
# random-problem parity: seeded sweep + hypothesis property
# ---------------------------------------------------------------------------

class TestRandomParity:
    @pytest.mark.parametrize("fabric", [False, True],
                             ids=["star", "fabric"])
    def test_seeded_random_problems(self, fabric):
        """40 seeded random problems per topology family: every backend
        within float32 tolerance of the float64 oracle."""
        rng = np.random.default_rng(20260808 + fabric)
        for _ in range(40):
            demands, paths, caps = random_problem(rng, fabric=fabric)
            golden, via_jnp, via_kernel = solve_all_backends(
                demands, paths, caps)
            np.testing.assert_allclose(via_jnp, golden, atol=TOL, rtol=0)
            np.testing.assert_allclose(via_kernel, golden, atol=TOL, rtol=0)

    def test_rates_feasible_and_demand_capped(self):
        """Vectorized rates never exceed demands or link capacities."""
        rng = np.random.default_rng(7)
        for _ in range(20):
            demands, paths, caps = random_problem(rng, fabric=True)
            mat = fluid.problem_matrix(demands, paths, caps)[:3]
            rates = fluid.fill_many([mat], backend="jnp")[0]
            assert np.all(rates <= np.asarray(demands) + TOL)
            load = {}
            for r, p in zip(rates, paths):
                for l in p:
                    load[l] = load.get(l, 0.0) + r
            for l, used in load.items():
                assert used <= caps[l] + TOL * len(paths)

    @given(data=st.data())
    @settings(max_examples=25, deadline=None)
    def test_property_random_topology(self, data):
        """Hypothesis drives the same generator through a drawn seed and
        topology family (skips when hypothesis is stubbed out)."""
        seed = data.draw(st.integers(min_value=0, max_value=2**31 - 1))
        fabric = data.draw(st.booleans())
        rng = np.random.default_rng(seed)
        demands, paths, caps = random_problem(rng, fabric=fabric)
        golden, via_jnp, via_kernel = solve_all_backends(demands, paths, caps)
        np.testing.assert_allclose(via_jnp, golden, atol=TOL, rtol=0)
        np.testing.assert_allclose(via_kernel, golden, atol=TOL, rtol=0)


# ---------------------------------------------------------------------------
# scenario-level parity on the pinned snapshots
# ---------------------------------------------------------------------------

class TestScenarioParity:
    @pytest.mark.parametrize("sid", ["S2", "S4", "F2", "F4", "J1"])
    def test_pinned_fill_problems(self, sid):
        """The snapshots' real fill problems (post-Metronome placement)
        agree across backends."""
        cluster, fw, wls = scheduled(sid)
        view = LinkView.from_registry(cluster, fw.registry)
        jobs = [j for wl in wls for j in wl.jobs]
        demands, paths, caps = view.fill_problem(jobs)
        assert demands, f"{sid}: no flows — parity test is vacuous"
        golden, via_jnp, via_kernel = solve_all_backends(demands, paths, caps)
        np.testing.assert_allclose(via_jnp, golden, atol=TOL, rtol=0)
        np.testing.assert_allclose(via_kernel, golden, atol=TOL, rtol=0)

    def test_engine_fill_matches_oracle(self, interpret_kernels):
        """FluidEngine.fill dispatches per backend onto the same problem;
        'kernel' runs the Pallas kernel (interpret mode here)."""
        cluster, fw, wls = scheduled("F4")
        view = LinkView.from_registry(cluster, fw.registry)
        demands, paths, caps = view.fill_problem(
            [j for wl in wls for j in wl.jobs])
        golden = fluid.FluidEngine("python").fill(demands, paths, caps)
        ran = interpret_kernels[("progressive_fill", "interpret")]
        for backend in ("jnp", "kernel"):
            got = fluid.FluidEngine(backend).fill(demands, paths, caps)
            np.testing.assert_allclose(got, golden, atol=TOL, rtol=0)
        assert interpret_kernels[("progressive_fill", "interpret")] == ran + 1

    def test_kernel_backend_refuses_without_tpu(self):
        """Off a TPU, 'kernel' without interpret=True raises and names the
        platform; it never substitutes the jnp reference."""
        mat = fluid.problem_matrix([5.0, 3.0], [("h0",), ("h0",)],
                                   {"h0": 4.0})[:3]
        with pytest.raises(RuntimeError, match="platform is 'cpu'"):
            fluid.fill_many([mat], backend="kernel")
        with pytest.raises(RuntimeError, match="needs a TPU"):
            fluid.FluidEngine("kernel").solve_batch(
                [([5.0], [("h0",)], {"h0": 4.0})])

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown fluid backend"):
            fluid.FluidEngine("numpy")
        with pytest.raises(ValueError, match="vectorized backend"):
            fluid.fill_many([], backend="python") or fluid.fill_many(
                [(np.zeros(1, np.float32), np.zeros((1, 1), np.float32),
                  np.ones(1, np.float32))], backend="python")


# ---------------------------------------------------------------------------
# bit-for-bit goldens: backend='python' IS the seed path
# ---------------------------------------------------------------------------

def _sim_equal(a, b):
    """Bit-for-bit SimResult equality (NaN-aware float maps)."""
    def eq(x, y):
        if isinstance(x, float) and isinstance(y, float):
            return (math.isnan(x) and math.isnan(y)) or x == y
        return x == y

    def map_eq(x, y):
        return set(x) == set(y) and all(eq(x[k], y[k]) for k in x)

    assert a.durations_ms == b.durations_ms
    assert map_eq(a.time_per_1000_iters_s, b.time_per_1000_iters_s)
    assert map_eq(a.link_utilization, b.link_utilization)
    assert eq(a.avg_bw_utilization, b.avg_bw_utilization)
    assert a.readjustments == b.readjustments
    assert map_eq(a.finish_times_ms, b.finish_times_ms)
    assert eq(a.total_completion_ms, b.total_completion_ms)
    assert a.iterations_done == b.iterations_done
    assert a.reconfigurations == b.reconfigurations


class TestPythonBackendGoldens:
    @pytest.mark.parametrize("sid", PINNED)
    def test_static_snapshots(self, sid):
        scen = snapshot_scenario(sid, n_iterations=N_ITER)
        default = run(scen, Policy("metronome"), CFG)
        explicit = run(scen, Policy("metronome", sim_backend="python"), CFG)
        _sim_equal(default.sim, explicit.sim)
        assert default.accepted == explicit.accepted
        assert default.placements == explicit.placements

    @pytest.mark.parametrize("sid", DYNAMIC_SNAPSHOTS)
    def test_dynamic_snapshots(self, sid):
        scen = dynamic_scenario(sid, n_iterations=N_ITER)
        default = run(scen, Policy("metronome"), CFG)
        explicit = run(scen, Policy("metronome", sim_backend="python"), CFG)
        _sim_equal(default.sim, explicit.sim)
        assert default.accepted == explicit.accepted

    def test_policy_name_encodes_backend(self):
        assert Policy("metronome").name == "metronome"
        assert Policy("metronome", sim_backend="jnp").name == \
            "metronome-fluid=jnp"
        assert Policy("metronome", sim_backend="python").name == \
            "metronome-fluid=python"


# ---------------------------------------------------------------------------
# incremental per-component memoization
# ---------------------------------------------------------------------------

class _Flow:
    def __init__(self, node, demand, links):
        self.node = node
        self.demand_gbps = demand
        self.links = links
        self.rate_gbps = 0.0


class TestIncrementalEngine:
    def _flows(self):
        # two affinity components: {hA} and {hB, uplink:1}
        return [_Flow("hA", 10.0, ("hA",)),
                _Flow("hA", 6.0, ("hA",)),
                _Flow("hB", 8.0, ("hB", "uplink:1")),
                _Flow("hB", 5.0, ("hB",))]

    def test_components(self):
        comps = fluid.affinity_components(
            [f.links for f in self._flows()])
        assert comps == [[0, 1], [2, 3]]

    def test_memo_hits_and_selective_invalidation(self):
        eng = fluid.FluidEngine("python", incremental=True)
        caps = {"hA": 12.0, "hB": 10.0, "uplink:1": 6.0}
        flows = self._flows()
        eng.assign(flows, caps.__getitem__)
        assert (eng.stats.misses, eng.stats.hits) == (2, 0)
        first = [f.rate_gbps for f in flows]

        eng.assign(flows, caps.__getitem__)  # unchanged: both memoized
        assert (eng.stats.misses, eng.stats.hits) == (2, 2)
        assert [f.rate_gbps for f in flows] == first

        caps["uplink:1"] = 3.0  # touches ONLY the {hB} component
        eng.assign(flows, caps.__getitem__)
        assert (eng.stats.misses, eng.stats.hits) == (3, 3)
        assert [f.rate_gbps for f in flows[:2]] == first[:2]
        assert flows[2].rate_gbps < first[2]

    def test_incremental_matches_full_solve(self):
        caps = {"hA": 12.0, "hB": 10.0, "uplink:1": 6.0}
        inc, full = self._flows(), self._flows()
        fluid.FluidEngine("python", incremental=True).assign(
            inc, caps.__getitem__)
        fluid.FluidEngine("python", incremental=False).assign(
            full, caps.__getitem__)
        # disjoint single-link components: per-component == global here
        for a, b in zip(inc, full):
            assert a.rate_gbps == pytest.approx(b.rate_gbps, abs=1e-9)

    def test_backend_defaults(self):
        assert fluid.FluidEngine("python").incremental is False
        assert fluid.FluidEngine("jnp").incremental is True
        assert fluid.FluidEngine("kernel").incremental is True


# ---------------------------------------------------------------------------
# corpus batching
# ---------------------------------------------------------------------------

class TestFillCorpus:
    def test_order_restored_across_buckets(self):
        """fill_corpus sorts by flow count internally; results must come
        back in caller order and equal the one-call fill_many answers."""
        rng = np.random.default_rng(11)
        probs, mats = [], []
        for _ in range(17):
            d, p, c = random_problem(rng, fabric=True)
            probs.append((d, p, c))
            mats.append(fluid.problem_matrix(d, p, c)[:3])
        want = fluid.fill_many(mats, backend="jnp")
        got = fluid.fill_corpus(mats, backend="jnp", chunk=4)
        assert len(got) == len(want)
        for g, w, (d, p, c) in zip(got, want, probs):
            np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
            np.testing.assert_allclose(
                g, fluid.fill_python(np.asarray(d, dtype=float), p, c),
                atol=TOL, rtol=0)

    def test_empty_corpus(self):
        assert fluid.fill_corpus([], backend="jnp") == []


# ---------------------------------------------------------------------------
# timers and spans of the rate solve (SimConfig.profile)
# ---------------------------------------------------------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span's
    opening and closing in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append(("open", name))

            def __exit__(self, *exc):
                rec.log.append(("close", name))

        return Span()


def _timers(stats):
    return (stats.batch_s, stats.key_s, stats.pack_s, stats.device_s)


class TestSolveTimers:
    def _problems(self, n=6, seed=21):
        rng = np.random.default_rng(seed)
        return [random_problem(rng, fabric=True) for _ in range(n)]

    def test_parts_nest_inside_the_batch(self):
        eng = fluid.FluidEngine("jnp")
        eng.timed = True
        probs = self._problems()
        eng.solve_batch(probs)          # misses: key, pack and device
        st = eng.stats
        assert st.misses == len(probs)
        assert min(_timers(st)) > 0.0
        assert st.key_s + st.pack_s + st.device_s <= st.batch_s
        device = st.device_s
        eng.solve_batch(probs)          # memo hits: no fill
        assert st.hits == len(probs)
        assert st.device_s == device

    def test_untimed_engine_books_nothing(self):
        eng = fluid.FluidEngine("jnp")
        eng.solve_batch(self._problems())
        assert _timers(eng.stats) == (0.0,) * 4
        assert "solves" not in dataclasses.asdict(eng.stats)

    def test_timing_leaves_the_answers_alone(self):
        mats = [fluid.problem_matrix(*p)[:3] for p in self._problems(9)]
        stats = fluid.FluidStats()
        timed = fluid.fill_corpus(mats, backend="jnp", chunk=4,
                                  bucket_shapes=True, timing=stats)
        plain = fluid.fill_corpus(mats, backend="jnp", chunk=4,
                                  bucket_shapes=True)
        for a, b in zip(timed, plain):
            np.testing.assert_array_equal(a, b)
        assert stats.pack_s > 0.0 and stats.device_s > 0.0
        assert (stats.batch_s, stats.key_s) == (0.0, 0.0)

    def test_spans_nest_in_solve_batch(self):
        """``fluid.solve_batch`` opens first and closes last; key, pack and
        device spans open and close inside it, one at a time."""
        rec = _Recorder()
        eng = fluid.FluidEngine("jnp")
        eng.timed, eng.annotate = True, rec
        eng.solve_batch(self._problems())
        log = rec.log
        assert log[0] == ("open", "fluid.solve_batch")
        assert log[-1] == ("close", "fluid.solve_batch")
        inner = log[1:-1]
        assert {n for _, n in inner} == {"fluid.key", "fluid.pack",
                                         "fluid.device"}
        # siblings: each span closes before the next one opens
        assert inner[0::2] == [("open", n) for _, n in inner[0::2]]
        assert inner[1::2] == [("close", n) for _, n in inner[0::2]]


# ---------------------------------------------------------------------------
# production trace generator
# ---------------------------------------------------------------------------

class TestProductionTrace:
    def test_exact_count_and_determinism(self):
        a = generate_production_trace(MODEL_FLEET, n_jobs=500, seed=42)
        b = generate_production_trace(MODEL_FLEET, n_jobs=500, seed=42)
        c = generate_production_trace(MODEL_FLEET, n_jobs=500, seed=43)
        assert len(a) == 500
        assert a == b
        assert a != c

    def test_sorted_and_fields_sane(self):
        trace = generate_production_trace(MODEL_FLEET, n_jobs=400, seed=1)
        times = [s.submit_time_s for s in trace]
        assert times == sorted(times)
        for s in trace:
            assert 60.0 <= s.duration_s <= 6 * 3600.0
            assert s.n_tasks >= 1
            assert s.model in MODEL_FLEET

    def test_diurnal_peak_vs_trough(self):
        """Arrival rate at the 14:00 peak beats the 02:00 trough clearly
        (amplitude 0.6 -> true ratio 4; demand a comfortable 2x)."""
        trace = generate_production_trace(MODEL_FLEET, n_jobs=6000, seed=5)

        def count(center_h):
            lo, hi = (center_h - 2) * 3600.0, (center_h + 2) * 3600.0
            return sum(1 for s in trace if lo <= s.submit_time_s < hi)

        assert count(14.0) > 2 * count(2.0)

    def test_heavy_tail_and_priority_mix(self):
        trace = generate_production_trace(MODEL_FLEET, n_jobs=3000, seed=9)
        durs = np.array([s.duration_s for s in trace])
        assert np.max(durs) > 8 * np.median(durs)  # lognormal right tail
        frac_hi = np.mean([bool(s.priority) for s in trace])
        assert 0.2 < frac_hi < 0.4  # high_priority_frac = 0.3
        mults = {s.n_tasks for s in trace}
        assert len(mults) >= 3  # task multipliers actually mix sizes

    def test_active_jobs_at(self):
        trace = [TraceJobSpec("M", 0.0, 10.0, 0, 1),
                 TraceJobSpec("M", 5.0, 10.0, 0, 1),
                 TraceJobSpec("M", 20.0, 1.0, 0, 1)]
        assert active_jobs_at(trace, 1.0) == [0]
        assert active_jobs_at(trace, 7.0) == [0, 1]
        assert active_jobs_at(trace, 12.0) == [1]
        assert active_jobs_at(trace, 30.0) == []


# ---------------------------------------------------------------------------
# per-family batched link solves (Score phase)
# ---------------------------------------------------------------------------

class TestSolveLinkBatch:
    @pytest.mark.parametrize("sid", ["S2", "F4", "J1"])
    def test_batch_equals_individual(self, sid):
        cluster, fw, _ = scheduled(sid)
        view = LinkView.from_registry(cluster, fw.registry)
        links = sorted(view.planning_links())
        specs = [(view, lid) for lid in links]
        batched = rotation.solve_link_batch(specs, fw.registry, mode="fast")
        for (score, scheme), lid in zip(batched, links):
            want_score, want = rotation.solve_link(view, fw.registry, lid,
                                                   mode="fast")
            assert score == want_score
            assert (scheme is None) == (want is None)
            if scheme is not None:
                assert scheme.jobs == want.jobs
                assert np.array_equal(scheme.shifts_slots, want.shifts_slots)
                assert scheme.base_ms == want.base_ms
                assert scheme.injected_ms == want.injected_ms


# ---------------------------------------------------------------------------
# process-mode sweeps + content-keyed cache
# ---------------------------------------------------------------------------

class TestSweepInfra:
    GRID_CFG = SimConfig(duration_ms=6_000.0, seed=3, jitter_std=0.01)

    def _grid(self):
        return ([snapshot_scenario("S2", n_iterations=10)],
                [Policy("metronome"), Policy("default")])

    @pytest.mark.slow
    def test_process_mode_matches_serial(self):
        scenarios, policies = self._grid()
        serial = sweep(scenarios, policies, self.GRID_CFG)
        procs = sweep(scenarios, policies, self.GRID_CFG,
                      workers=2, mode="process")
        assert serial.to_json_dict(include_durations=True) == \
            procs.to_json_dict(include_durations=True)

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="thread.*process"):
            sweep(*self._grid(), mode="threads")

    @pytest.mark.parametrize("where", ["policy", "sim_config"])
    def test_process_mode_refuses_device_backend(self, where):
        """A chip belongs to one process: no spawned worker may run a
        device fluid backend, whether the policy or the config selects
        it.  The refusal comes before any cell runs."""
        scenarios, _ = self._grid()
        if where == "policy":
            policies = [Policy("default"),
                        Policy("metronome", sim_backend="kernel")]
            cfg = self.GRID_CFG
        else:
            policies = [Policy("default")]
            cfg = dataclasses.replace(self.GRID_CFG, fluid_backend="jnp")
        with pytest.raises(ValueError, match="one process"):
            sweep(scenarios, policies, cfg, workers=2, mode="process")

    def test_cache_roundtrip_and_keying(self, tmp_path):
        from benchmarks import cache

        scenarios, policies = self._grid()
        key = cache.fingerprint_grid(scenarios, policies, self.GRID_CFG)
        assert key == cache.fingerprint_grid(scenarios, policies,
                                             self.GRID_CFG)
        # a policy knob changes the content key
        assert key != cache.fingerprint_grid(
            scenarios, [Policy("metronome", sim_backend="python")],
            self.GRID_CFG)
        # a sim-config change does too
        assert key != cache.fingerprint_grid(
            scenarios, policies, SimConfig(duration_ms=7_000.0, seed=3))

        assert cache.load(str(tmp_path), key) is None  # cold miss
        res = sweep(scenarios, policies, self.GRID_CFG)
        cache.store(str(tmp_path), key, res)
        back = cache.load(str(tmp_path), key)
        assert back is not None
        assert back.to_json_dict(include_durations=True) == \
            res.to_json_dict(include_durations=True)

        # corrupt entries are a miss, not a crash
        (path,) = [p for p in os.listdir(tmp_path) if key in p]
        with open(tmp_path / path, "w") as f:
            f.write("{not json")
        assert cache.load(str(tmp_path), key) is None


# ---------------------------------------------------------------------------
# diff_bench regression gates (scripts/diff_bench.py)
# ---------------------------------------------------------------------------

def _diff_bench():
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "diff_bench", os.path.join(root, "scripts", "diff_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestDiffBench:
    def test_sweep_gate(self, tmp_path):
        db = _diff_bench()
        doc = {"sweeps": [{"meta": {"origin": "bench_x"}, "cells": [
            {"scenario": "S2", "policy": "metronome", "status": "ok",
             "result": {"jct": 1.0, "samples": [1, 2, 3]}}]}]}
        assert db.diff_sweeps(doc, doc, 1e-6) == []
        drift = json.loads(json.dumps(doc))
        drift["sweeps"][0]["cells"][0]["result"]["jct"] = 1.5
        assert any("jct" in p for p in db.diff_sweeps(doc, drift, 1e-6))
        gone = {"sweeps": []}
        assert any("missing" in p for p in db.diff_sweeps(doc, gone, 1e-6))
        # list leaves compare as lengths only (trajectories are not pinned)
        jig = json.loads(json.dumps(doc))
        jig["sweeps"][0]["cells"][0]["result"]["samples"] = [9, 9, 9]
        assert db.diff_sweeps(doc, jig, 1e-6) == []

    def test_timing_and_trace_gates(self):
        db = _diff_bench()
        base = {"rows": [{"origin": "b", "name": "r", "us_per_call": 10.0}]}
        slow = {"rows": [{"origin": "b", "name": "r", "us_per_call": 900.0}]}
        assert db.diff_timings(base, base, 25.0) == []
        assert any("slower" in p for p in db.diff_timings(base, slow, 25.0))

        trace = {"rows": [
            {"name": "py", "backend": "python", "speedup_vs_python": 1.0},
            {"name": "jnp", "backend": "jnp", "speedup_vs_python": 60.0}]}
        assert db.diff_trace(trace, trace, 50.0) == []
        sagged = json.loads(json.dumps(trace))
        sagged["rows"][1]["speedup_vs_python"] = 8.0
        assert any("speedup" in p for p in db.diff_trace(trace, sagged, 50.0))
