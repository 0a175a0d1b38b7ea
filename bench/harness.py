"""One run of one cell: build it from its files, warm it up, measure a
window of fixed chunks of simulated time, check what it produced, print
the result line.

The entry point the window drives is ``ClusterSimulator.run``, built the
way ``experiment.run`` builds a trace scenario (that construction is
copied here so that the yardstick does not move with the program).
Simulated time advances in chunks of ``chunk_sim_s``: each chunk raises
``duration_ms`` and calls ``run()`` again, and the window ends at the
first chunk edge after ``--seconds``.  Chunk edges lie at the same
simulated times on every commit, so two commits that reach the same
simulated time have done the same work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import random
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from . import devtrace, reference, traffic
from .probes import CHUNK_SPAN, Probes
from .spec import BENCH_DIR, Cell, load_part

WINDOW_SPAN = "bench.window"
OPEN_ENDED_ITERATIONS = 1_000_000_000


# --------------------------------------------------------------- building
def build(cell: Cell, seed: int, backend: Optional[str] = None):
    """(simulator, jobs) of ``cell`` for run seed ``seed``; ``backend``
    overrides the configuration's fluid backend (CPU rehearsal)."""
    from repro.core.cluster import Resources
    from repro.core.events import JobDeparture
    from repro.core.experiment import Policy, build_scheduler
    from repro.core.framework import SchedulingFramework
    from repro.core.simulator import ClusterSimulator, SimConfig
    from repro.core.workload import HIGH, LOW, Workload, make_job

    cfg, mix = cell.config, cell.traffic
    fleet = cfg["fleet"]
    pod = cfg["pod"]
    ts = float(mix["time_scale"])
    jobs = traffic.job_stream(mix, fleet, seed)
    workloads, events = [], []
    for i, spec in enumerate(jobs):
        f = fleet[spec.model]
        name = job_name(spec, i)
        job = make_job(name, n_tasks=spec.n_tasks, period_ms=f["period_ms"],
                       duty=f["duty"], bw_gbps=f["bw_gbps"],
                       priority=HIGH if spec.high_priority else LOW,
                       resources=Resources(cpu=pod["cpu"], mem=pod["mem"],
                                           gpu=pod["gpu"]),
                       spread=int(pod["spread"]),
                       n_iterations=OPEN_ENDED_ITERATIONS,
                       submit_time_s=spec.submit_s * ts, model=spec.model)
        workloads.append(Workload(name=name, jobs=[job]))
        events.append(JobDeparture(time_ms=departure_ms(spec, ts), job=name))

    knobs = cfg["sim"]
    policy = Policy(**cfg["policy"],
                    sim_backend=backend or knobs["fluid_backend"])
    sim_cfg = SimConfig(
        duration_ms=0.0, seed=seed, jitter_std=float(knobs["jitter_std"]),
        startup_ms=float(knobs["startup_ms"]),
        latency_penalty_ms_per_tau=float(knobs["latency_penalty_ms_per_tau"]),
        fluid_backend=policy.sim_backend, profile=True)
    cluster = make_cluster(cfg["cluster"], seed)
    plugin, controller = build_scheduler(policy)
    fw = SchedulingFramework(cluster, plugin)
    sim = ClusterSimulator(
        cluster, [], sim_cfg, controller=controller, background=[],
        registry=fw.registry, framework=fw, arrivals=workloads,
        events=events, offline_recalc=not policy.skip_third_stage)
    return sim, jobs


def job_name(spec, index: int) -> str:
    return f"{spec.model.lower()}-{index}"


def departure_ms(spec, time_scale: float) -> float:
    return (spec.submit_s + spec.duration_s) * time_scale * 1e3


def make_cluster(layout: dict, seed: int):
    """The cluster a configuration lists: its workers, each with its
    resources and NIC, one switch hop apart; with ``leaves`` (leaf ->
    workers) and ``oversubscription``, a leaf-spine whose uplinks carry
    their leaf's NICs over that factor.

    The seed permutes the names of workers alike in resources, NIC and
    leaf among their places in the list.  The scheduler breaks ties by
    that order, so each seed places the same jobs on an isomorphic set of
    workers: the same work under other names."""
    from repro.core.cluster import Cluster, Node, Resources
    from repro.core.topology import Topology

    leaf_of = {m: leaf for leaf, ms in layout.get("leaves", {}).items()
               for m in ms}
    specs = list(layout["nodes"])
    alike: Dict[tuple, List[int]] = {}
    for i, n in enumerate(specs):
        key = (n["cpu"], n["mem"], n["gpu"], n["bw_gbps"],
               leaf_of.get(n["name"]))
        alike.setdefault(key, []).append(i)
    rng = random.Random(seed)
    names = [n["name"] for n in specs]
    for places in alike.values():
        drawn = [names[i] for i in places]
        rng.shuffle(drawn)
        for i, name in zip(places, drawn):
            names[i] = name
    nodes = [Node(name, Resources(cpu=n["cpu"], mem=n["mem"], gpu=n["gpu"]),
                  bw_gbps=n["bw_gbps"]) for name, n in zip(names, specs)]
    topo = None
    if "leaves" in layout:
        topo = Topology.leaf_spine(
            layout["leaves"], host_bw_gbps={n.name: n.bw_gbps for n in nodes},
            oversubscription=float(layout["oversubscription"]))
    return Cluster(nodes, topology=topo)


def advance(sim, to_ms: float):
    """Run the simulator on to simulated time ``to_ms``."""
    sim.config = dataclasses.replace(sim.config, duration_ms=to_ms)
    return sim.run()


def fill_shapes(mix: dict) -> List[tuple]:
    """The ``(F, L)`` pads of every fill bucket the cell can produce:
    powers of two from 4 up to the mix's ``fill_max_flows`` and
    ``fill_max_links``.  A problem has at most one link more than it has
    flows (its flows connect its links), so ``L <= 2F`` after rounding."""
    flows = _pow2_range(int(mix["fill_max_flows"]))
    links = _pow2_range(int(mix["fill_max_links"]))
    return [(f, l) for f in flows for l in links if l <= 2 * f]


def _pow2_range(top: int) -> List[int]:
    out, p = [], 4
    while p <= max(top, 4):
        out.append(p)
        p <<= 1
    return out


def precompile(mix: dict, backend: str) -> int:
    """Compile (or load from the cache) every fill bucket of the mix by
    solving a batch of neutral problems at each shape."""
    from repro.core import fluid

    chunk = 64  # fluid.fill_corpus's bucket batch
    dummy = (np.zeros(1, dtype=np.float32), np.zeros((1, 1), np.float32),
             np.ones(1, dtype=np.float32))
    shapes = fill_shapes(mix)
    for f, l in shapes:
        fluid.fill_many([dummy] * chunk, backend=backend, pad_to=(f, l))
    return len(shapes)


# ----------------------------------------------------------------- metrics
def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return load_part("metrics", name, "read", bench_dir)


def read_metrics(entries: List[dict], win, bench_dir: Path) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = load_reader(m["name"], bench_dir)(win)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ checks
def checks(cell: Cell, probes: Probes, sim, jobs, win) -> Dict[str, dict]:
    """Every number compared, with its limit: ``value <= limit`` passes."""
    cfg = cell.config
    limits = cfg["check"]
    err = 0.0
    for demands, paths, caps, rates in probes.samples:
        want = reference.fill(demands, paths, caps)
        err = max([err] + [abs(float(r) - w) for r, w in zip(rates, want)])
    bad_admissions = sum(cell.admission(rec) for rec in probes.admissions)
    gap = progress_gap(cell, probes, sim, jobs, win.edges)
    return {
        "fill_err_gbps": {"value": err, "limit": limits["fill_err_gbps"]},
        "bad_admissions": {"value": bad_admissions, "limit": 0},
        "progress_gap_ms": {"value": gap, "limit": limits["progress_gap_ms"]},
        "clock_gap_ms": {"value": win.clock_gap_ms, "limit": 0.0},
        "nonfinite_solves": {"value": probes.nonfinite, "limit": 0},
    }


def snapshot(sim, probes: Probes) -> dict:
    """The program's state at a chunk edge, as the reference follows it:
    per admitted job still running, its workers, its phase, the end of a
    timed phase, the Gb left on the flow from each worker, a realign
    pending for its next compute phase and a pause pending there; the
    controller's answers then in force; and how many admissions,
    controller answers and realigns had been recorded."""
    tbl = sim._flows
    jobs = {}
    for name, st in sim.jobs.items():
        if st.phase == "done":
            continue
        left = {}
        for slot in (st.flow_slots if st.flow_slots is not None else ()):
            left[tbl.paths[slot][0]] = float(tbl.remaining[slot])
        jobs[name] = {"workers": probes.placed[name], "phase": st.phase,
                      "end": None if math.isinf(st.phase_end)
                      else float(st.phase_end),
                      "left": left, "pending": bool(st.realign_pending),
                      "pause": float(st.pending_pause_ms)}
    return {"t_ms": float(sim.now), "jobs": jobs,
            "control": probes.control_state(),
            "admissions": len(probes.admissions),
            "controls": len(probes.control),
            "realigns": len(probes.realigns)}


def program_completions(sim, probes: Probes) -> Dict[str, list]:
    """When each iteration of each admitted job ended in the program's run:
    its start (admission plus start-up, plus the wait for its circle offset
    under a controller) plus its iteration times so far."""
    out = {}
    for rec in probes.admissions:
        if rec["admitted"]:
            st = sim.jobs[rec["job"]]
            t = st.start_time
            ends = []
            for d in st.durations_ms:
                t += d
                ends.append(t)
            out[rec["job"]] = ends
    return out


def progress_gap(cell: Cell, probes: Probes, sim, jobs, edges) -> float:
    """Widest gap between the program's and the reference's iteration
    completion times over the first ``follow_sim_s`` of every chunk of the
    window (the whole chunk where it is shorter), each followed by the
    reference from the program's state at the chunk's edge: the fluid
    model amplifies rounding too fast to follow a whole window (PERF.md)."""
    cfg = cell.config
    ts = float(cell.traffic["time_scale"])
    horizon = 1e3 * min(float(cfg["check"]["follow_sim_s"]),
                        float(cell.traffic["chunk_sim_s"]))
    startup = float(cfg["sim"]["startup_ms"])
    spec_of, departures = {}, {}
    for i, spec in enumerate(jobs):
        f = cfg["fleet"][spec.model]
        name = job_name(spec, i)
        comm = f["period_ms"] * f["duty"]
        spec_of[name] = {"compute_ms": f["period_ms"] - comm, "comm_ms": comm,
                         "bw_gbps": f["bw_gbps"],
                         "high": bool(spec.high_priority)}
        departures[name] = departure_ms(spec, ts)
    done = program_completions(sim, probes)
    gap = 0.0
    for edge in edges:
        t0, t1 = edge["t_ms"], edge["t_ms"] + horizon
        later = [(r["t_ms"], r["job"], r["placed"], r["control_after"])
                 for r in probes.admissions[edge["admissions"]:]
                 if r["admitted"]]
        control = [(c["t_ms"], c["state"])
                   for c in probes.control[edge["controls"]:]
                   if c["t_ms"] < t1]
        realigns = [(r["t_ms"], r["jobs"])
                    for r in probes.realigns[edge["realigns"]:]
                    if r["t_ms"] < t1]
        ref = reference.follow(spec_of, edge, later, departures,
                               cfg["cluster"], t1, startup, control,
                               realigns)
        got = {name: [x for x in done.get(name, []) if t0 <= x < t1]
               for name in ref}
        gap = max(gap, reference.progress_gap(got, ref, t1))
    return gap


# -------------------------------------------------------------------- run
def require_chips(n: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < n:
        raise SystemExit(
            f"bench: the cell needs {n} TPU chip(s); JAX found "
            f"{dev['count']} device(s) of platform {dev['platform']!r}. "
            "No result.")
    dev["count"] = n
    return dev


def enable_cache(root: Path) -> None:
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(root)
    # the fill buckets compile in well under a second each; cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        root: Path, t_start: float, rehearse: bool = False,
        bench_dir: Path = BENCH_DIR) -> dict:
    """Measure one run of ``cell`` and return the result line's object.

    ``rehearse=True`` skips the look for a chip, runs the fluid solve on
    ``'jnp'``, and reports its readings under ``rehearsal_metrics``, never
    under a metric's name."""
    if rehearse:
        import jax
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
        backend = "jnp"
    else:
        device = require_chips(cell.chips)
        backend = None
    enable_cache(root)
    mix = cell.traffic
    sim, jobs = build(cell, seed, backend)
    probes = Probes(sim, seed=seed, sample_solves=int(mix["sample_solves"]),
                    trace=trace, policy=cell.config["policy"])
    n_shapes = precompile(mix, backend or cell.config["sim"]["fluid_backend"])
    ts = float(mix["time_scale"])
    chunk_ms = float(mix["chunk_sim_s"]) * 1e3
    end_ms = traffic.horizon_ms(jobs, ts)
    t_ms = float(mix["warmup_sim_s"]) * 1e3
    advance(sim, t_ms)
    setup_s = time.perf_counter() - t_start

    prof0 = dataclasses.asdict(sim.profile)
    memo0 = dataclasses.asdict(sim.fluid.stats)
    corpus0 = dataclasses.asdict(sim.fluid.corpus_stats)
    iters_at_open = {n: len(st.durations_ms) for n, st in sim.jobs.items()}
    tracedir = None
    if trace:
        import jax
        tracedir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tracedir, profiler_options=opts)
    probes.on = True
    clock_gap = 0.0
    edges: List[dict] = []
    sim_start = t_ms
    t0 = time.perf_counter()
    error = None
    try:
        with _span(WINDOW_SPAN, trace):
            while True:
                edges.append(snapshot(sim, probes))
                t_ms += chunk_ms
                with _span(CHUNK_SPAN, trace):
                    advance(sim, t_ms)
                clock_gap = max(clock_gap, abs(sim.now - t_ms))
                if time.perf_counter() - t0 >= seconds or t_ms >= end_ms:
                    break
    except Exception:  # the run is reported, not lost
        error = traceback.format_exc()
    wall = time.perf_counter() - t0
    probes.on = False
    reduction = None
    if trace:
        import jax
        jax.profiler.stop_trace()
        try:
            reduction = _reduce_trace(tracedir, device["count"])
        except Exception:  # a traced run without its reduction is not sound
            if not rehearse:  # a CPU trace has no device plane to read
                error = error or traceback.format_exc()
        shutil.rmtree(tracedir, ignore_errors=True)
    memory_peak = None
    if not rehearse:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
    probes.close()

    iterations = sum(len(st.durations_ms) - iters_at_open.get(n, 0)
                     for n, st in sim.jobs.items())
    win = SimpleNamespace(
        wall_s=wall, sim_s=(t_ms - sim_start) / 1e3, setup_s=setup_s,
        profile=_delta(prof0, dataclasses.asdict(sim.profile)),
        memo=_delta(memo0, dataclasses.asdict(sim.fluid.stats)),
        corpus=_delta(corpus0, dataclasses.asdict(sim.fluid.corpus_stats)),
        fill_bytes=probes.fill_bytes, trace=reduction,
        peaks=(_peaks(bench_dir, device["kind"])
               if trace and not rehearse else None),
        edges=edges, iterations=iterations, clock_gap_ms=clock_gap)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(entries, win, bench_dir) if error is None else {}
    attempted = probes.admit_calls + probes.solve_calls
    failed = probes.failed + (1 if error else 0)
    t_check = time.perf_counter()
    compared = {}
    if error is None:
        try:
            compared = checks(cell, probes, sim, jobs, win)
        except Exception:  # a run its references cannot judge is not correct
            error = traceback.format_exc()
            failed += 1
    check_s = time.perf_counter() - t_check
    correct = error is None and all(
        c["value"] <= c["limit"] for c in compared.values())
    counts = {
        "admission_attempts": probes.admit_calls,
        "refusals": sum(not r["admitted"] for r in probes.admissions),
        "solve_calls": probes.solve_calls,
        "solves_checked": len(probes.samples),
        "fill_calls": probes.fill_calls,
        "chunks_sim_s": win.sim_s, "window_wall_s": wall,
        "iterations": iterations, "fill_shapes_warmed": n_shapes,
        "window_compiles": probes.compiles,
        "admissions_judged": len(probes.admissions), "check_s": check_s,
    }
    print("bench counts: " + json.dumps(counts), file=sys.stderr)
    if error:
        print(error, file=sys.stderr)
    dev_out = dict(device)
    dev_out["memory_peak_bytes"] = memory_peak
    if reduction is not None:
        dev_out["busy_s"] = reduction.busy_s
        dev_out["window_s"] = reduction.window_s
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if rehearse:
        out["metrics"] = {}
        out["rehearsal_metrics"] = metrics
    else:
        out["metrics"] = metrics
    out["device"] = dev_out
    if reduction is not None:
        out["breakdown"] = {"device_ops": reduction.top_ops(),
                            "idle_gaps": reduction.gaps}
    out["checks"] = compared
    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return out


def _reduce_trace(tracedir: str, n_devices: int):
    paths = sorted(Path(tracedir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no .xplane.pb in {tracedir}")
    dev, host = devtrace.read_xplane(paths[-1])
    win = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    return devtrace.reduce(dev, host, win[0], n_devices)


def _peaks(bench_dir: Path, kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "peaks.json")
    return table["devices"][kind]


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before
            if isinstance(before[k], (int, float))}


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)
