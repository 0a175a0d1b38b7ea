"""Bring-up smoke: the Metronome scheduler and fluid simulator on one TPU.

    python chip_smoke.py

Drives the main path once, in this one process, and checks every result
against the repository's own oracle:

1. End to end.  The paper's full policy (Filter, Score, the joint rotation
   planner, offline recalculation, reconfiguration) admits a 300-job
   production trace online onto a 256-host leaf-spine fabric through
   ``experiment.run``; the simulator's rate solves run on the compiled
   ``metronome_fill`` kernel (``sim_backend='kernel'``).  The same cell on
   the python oracle backend must give identical placements and per-job
   iterations, and sampled in-loop kernel solves must match ``fill_python``
   within 1e-6.
2. Fill corpus.  The 10k-job, 1,024-snapshot corpus of
   ``benchmarks/bench_trace_throughput.py`` (~780k flows) through
   ``fill_corpus(backend='kernel')`` against ``fill_python``, within 1e-6.
3. Score kernel.  ``rotation.joint_solve_batch(backend='kernel')`` on J1's
   conflicted component and F4's uplink component, each as a family of
   capacity variants, must give the shifts of ``backend='numpy'``; the
   stacked ``metronome_score_multilink_batch`` kernel must have run.

Earlier lines report wall seconds per phase and the counts.  The last line
of standard output is ``{"ok": true, "device": {...}}``.  The script exits
non-zero, and prints no such line, when JAX finds no TPU or any check
fails.
"""
from __future__ import annotations

import functools
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TOL = 1e-6               # max-abs-err of a kernel solve vs fill_python (Gbps)
FILL_BACKEND = "kernel"  # the simulator's fluid backend under test
KERNEL_MODE = "compiled"  # the ops.DISPATCHES mode the kernels must take

# end-to-end cell: 16 leaves x 16 hosts, 25 Gbps NICs, 2:1 uplinks
FABRIC = dict(n_leaves=16, hosts_per_leaf=16, bw_gbps=25.0,
              oversubscription=2.0)
N_JOBS = 300
TIME_SCALE = 0.06
SAMPLE_STRIDE = 7

# fill corpus of benchmarks/bench_trace_throughput.py at full size
CORPUS_JOBS = 10_000
CORPUS_SNAPSHOTS = 1024

# link allocatable fractions that turn one joint component into a family
# of Score problems (same jobs and periods, different capacities)
CAP_FRACTIONS = (1.0, 0.9, 0.8, 0.7)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


@contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    print(f"== {name}", flush=True)
    yield
    print(f"== {name}: {time.perf_counter() - t0:.3f} s wall", flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(dev: dict) -> None:
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev['platform']!r}); nothing was run")


def expect_dispatch(op: str, before: int, what: str) -> int:
    """The kernel ``op`` ran in ``KERNEL_MODE`` since the count ``before``;
    returns how many times."""
    from repro.kernels import ops
    ran = ops.DISPATCHES[(op, KERNEL_MODE)] - before
    check(ran > 0, f"{what}: no {KERNEL_MODE} {op} dispatch")
    return ran


def dispatches(op: str) -> int:
    from repro.kernels import ops
    return ops.DISPATCHES[(op, KERNEL_MODE)]


def max_err(got, want) -> float:
    import numpy as np
    return max((float(np.max(np.abs(np.asarray(g) - np.asarray(w))))
                for g, w in zip(got, want) if len(w)), default=0.0)


def end_to_end() -> dict:
    from benchmarks.bench_dynamic_throughput import TRACE_KW, run_trace_sim
    from repro.configs.metronome_testbed import MODEL_FLEET, trace_scenario
    from repro.core import fluid
    from repro.core.cluster import make_fabric_cluster
    from repro.core.experiment import Policy, run
    from repro.core.simulator import SimConfig
    from repro.core.trace import generate_production_trace

    trace = generate_production_trace(MODEL_FLEET, n_jobs=N_JOBS, seed=7,
                                      **TRACE_KW)
    scen = trace_scenario(
        trace, time_scale=TIME_SCALE, name="fabric256-trace300",
        cluster_factory=functools.partial(make_fabric_cluster, **FABRIC))
    horizon_ms = max(s.submit_time_s + s.duration_s
                     for s in trace) * TIME_SCALE * 1e3
    cfg = SimConfig(duration_ms=horizon_ms + 1_000.0, seed=3,
                    jitter_std=0.01, profile=True)
    device = Policy("metronome", sim_backend=FILL_BACKEND)
    oracle = Policy("metronome", sim_backend="python")

    before = dispatches("progressive_fill")
    t0 = time.perf_counter()
    res_d = run(scen, device, cfg)
    t_device = time.perf_counter() - t0
    fills = expect_dispatch("progressive_fill", before, "end-to-end cell")
    t0 = time.perf_counter()
    res_o = run(scen, oracle, cfg)
    t_oracle = time.perf_counter() - t0
    prof = res_d.sim.profile
    print(f"{device.name}: {t_device:.3f} s; {oracle.name}: "
          f"{t_oracle:.3f} s; admitted {len(res_d.accepted)}/{N_JOBS}; "
          f"ticks {prof.ticks}; solves {prof.solves}; "
          f"fill dispatches {fills}", flush=True)
    print("phase seconds (kernel run): " + json.dumps(
        {k: round(v, 3) for k, v in prof.phase_seconds().items()}))
    check(sorted(res_d.accepted) == sorted(res_o.accepted),
          "admitted jobs differ from the python oracle")
    check(res_d.placements == res_o.placements,
          "placements differ from the python oracle")
    check(res_d.sim.iterations_done == res_o.sim.iterations_done,
          "per-job iterations differ from the python oracle")

    # the same cell with the live engine in reach, sampling in-loop solves
    sim, _ = run_trace_sim(scen, device, cfg)
    sim.fluid.sample_stride = SAMPLE_STRIDE
    res_s = sim.run()
    check(res_s.iterations_done == res_d.sim.iterations_done,
          "the sampled rerun diverged from experiment.run")
    samples = sim.fluid.samples
    check(len(samples) > 0, "no in-loop solve was sampled")
    err = max_err([s[3] for s in samples],
                  [fluid.fill_python(s[0], s[1], s[2]) for s in samples])
    corpus = sim.fluid.corpus_stats
    print(f"sampled solves {len(samples)}; max_abs_err vs fill_python "
          f"{err:.3e}; buckets {corpus.buckets}; flow occupancy "
          f"{corpus.flow_occupancy:.4f}", flush=True)
    check(err <= TOL, f"in-loop solve error {err:.3e} > {TOL}")
    return {"admitted": len(res_d.accepted), "solves": prof.solves,
            "fill_dispatches": fills, "sampled": len(samples),
            "max_abs_err": err}


def fill_corpus() -> dict:
    import numpy as np
    from benchmarks.bench_trace_throughput import snapshot_problem
    from repro.configs.metronome_testbed import MODEL_FLEET
    from repro.core import fluid
    from repro.core.trace import generate_production_trace

    trace = generate_production_trace(MODEL_FLEET, n_jobs=CORPUS_JOBS, seed=7)
    horizon = max(s.submit_time_s for s in trace)
    probs = [snapshot_problem(trace, horizon * (i + 0.5) / CORPUS_SNAPSHOTS)
             for i in range(CORPUS_SNAPSHOTS)]
    probs = [p for p in probs if p[0]]
    mats = [fluid.problem_matrix(d, p, c)[:3] for d, p, c in probs]
    n_flows = sum(len(p[0]) for p in probs)
    gold = [fluid.fill_python(np.asarray(d, dtype=float), p, c)
            for d, p, c in probs]

    before = dispatches("progressive_fill")
    t0 = time.perf_counter()
    rates = fluid.fill_corpus(mats, backend=FILL_BACKEND)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = fluid.fill_corpus(mats, backend=FILL_BACKEND)
    t_warm = time.perf_counter() - t0
    fills = expect_dispatch("progressive_fill", before, "fill corpus")
    err = max_err(rates, gold)
    print(f"problems {len(probs)}; flows {n_flows}; dispatches {fills}; "
          f"first call (compile included) {t_first:.3f} s; warm call "
          f"{t_warm:.3f} s; max_abs_err vs fill_python {err:.3e}",
          flush=True)
    check(err <= TOL, f"corpus error {err:.3e} > {TOL}")
    check(all(np.array_equal(a, b) for a, b in zip(rates, again)),
          "the corpus fill is not deterministic")
    return {"problems": len(probs), "flows": n_flows, "max_abs_err": err}


def _component_family(sid: str, conflicted_only: bool):
    """(registry, [(view, links)]) for ``sid``'s joint component with every
    link's allocatable share at each of ``CAP_FRACTIONS``; None when it has
    no such component."""
    from repro.configs.metronome_testbed import make_snapshot
    from repro.core import rotation
    from repro.core.contention import LinkView
    from repro.core.controller import StopAndWaitController
    from repro.core.framework import SchedulingFramework
    from repro.core.scheduler import MetronomePlugin

    cluster, wls, _ = make_snapshot(sid, n_iterations=50)
    fw = SchedulingFramework(
        cluster, MetronomePlugin(controller=StopAndWaitController()))
    for wl in wls:
        check(fw.schedule_workload(wl), f"{sid}: {wl.name} not admitted")
    view = LinkView.from_registry(cluster, fw.registry)
    schemes = {}
    for lid in view.planning_links():
        scheme = rotation.solve_link(view, fw.registry, lid)[1]
        if scheme is not None:
            schemes[lid] = scheme
    comps = [links for links, conflicted
             in rotation.conflicted_components(schemes)
             if len(links) > 1 and (conflicted or not conflicted_only)]
    if not comps:
        return None
    specs = []
    for frac in CAP_FRACTIONS:
        cl = cluster.copy()
        for node in cl.nodes.values():
            node.allocatable_gbps = frac * node.bw_gbps
        for lid in cl.topology.uplink_ids:
            link = cl.topology.link(lid)
            link.allocatable_gbps = frac * link.capacity_gbps
        specs.append((LinkView.from_registry(cl, fw.registry), comps[0]))
    return fw.registry, specs


def score_kernel() -> dict:
    import numpy as np
    from repro.core import rotation

    out = {}
    for sid, conflicted_only in (("J1", True), ("F4", False)):
        fam = _component_family(sid, conflicted_only)
        if fam is None:
            print(f"{sid}: no joint component", flush=True)
            continue
        registry, specs = fam
        before = dispatches("score_multilink_batch")
        got = rotation.joint_solve_batch(specs, registry, backend="kernel")
        ran = expect_dispatch("score_multilink_batch", before,
                              f"{sid} Score family")
        want = rotation.joint_solve_batch(specs, registry, backend="numpy")
        check(all(g is not None and w is not None
                  for g, w in zip(got, want)), f"{sid}: unsolved problem")
        check(all(np.array_equal(g.shifts, w.shifts)
                  for g, w in zip(got, want)),
              f"{sid}: kernel shifts differ from numpy")
        print(f"{sid}: component {specs[0][1]}; {len(specs)} problems; "
              f"stacked dispatches {ran}; shifts equal; scores "
              f"{[round(g.score, 4) for g in got]}", flush=True)
        out[sid] = len(specs)
    check("J1" in out, "J1 has no conflicted component")
    return out


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(ROOT)
    dev = device_info()
    print(f"device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    require_tpu(dev)
    with phase("end to end: 256-host fabric, 300-job trace, full policy"):
        end_to_end()
    with phase("fill corpus: 10k-job trace, 1,024 snapshots"):
        fill_corpus()
    with phase("Score kernel: J1 and F4 joint components"):
        score_kernel()
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
