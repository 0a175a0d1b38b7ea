"""Fluid-engine throughput on a 10k-job production trace.

The tentpole acceptance check of the backend-swappable fluid engine
(DESIGN.md section 16): sample active-set snapshots of a
:func:`~repro.core.trace.generate_production_trace` trace (diurnal
arrivals, heavy-tailed sizes), express each snapshot as one (flows x
links) fill problem, then rate-solve the whole corpus two ways:

  * ``python`` — the golden oracle: :func:`repro.core.fluid.fill_python`
    sequentially, one per-flow progressive-filling loop per snapshot (what
    ``FluidEngine(backend='python')`` does inside the simulator).
  * ``jnp`` / ``kernel`` — :func:`repro.core.fluid.fill_corpus`:
    size-bucketed (B, F, L) blocks, each solved in one batched
    fixed-point dispatch.  The ``kernel`` row needs a TPU and is left out
    elsewhere.

The snapshots land on a congested dumbbell fabric — two racks of four
hosts with heterogeneous NIC tiers (1/2.5/10/40 Gbps) joined by a 10 Gbps
trunk, tasks placed with a load-aware skew and ~10% of jobs spanning both
racks.  At peak-hour active sets (~800 flows) every link is oversubscribed
and the distinct per-link fair-share levels saturate one at a time, so the
progressive fill runs its full multi-round course instead of collapsing in
a round or two — the regime the per-flow python loop is worst at and the
whole reason the vectorized backends exist.

Rows land in ``BENCH_trace_throughput.json`` (run.py ``--trace-out``);
the vectorized rows' ``speedup_vs_python`` is the >=50x acceptance
metric, and ``max_abs_err_vs_python`` pins the backends to the oracle.
"""
from __future__ import annotations

import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.configs.metronome_testbed import MODEL_FLEET
from repro.core import fluid
from repro.core.trace import (TraceJobSpec, active_jobs_at,
                              generate_production_trace)

from . import common
from .common import emit, record_trace_row

# dumbbell fabric: 2 racks x 4 hosts with tiered NICs, one shared trunk
NIC_TIERS = (1.0, 2.5, 10.0, 40.0)
# task placement skew, load-aware-ish: big NICs soak up most tasks, so the
# per-link fair-share levels (cap / flow count) stay distinct and the
# links saturate in staggered rounds
PLACE_WEIGHTS = (0.08, 0.12, 0.30, 0.50)
TRUNK_GBPS = 10.0
CROSS_RACK_MOD = 10  # every 10th job spans both racks (crosses the trunk)

Problem = Tuple[List[float], List[Tuple[str, ...]], Dict[str, float]]


def _pick_host(h: int) -> int:
    """Deterministic weighted host tier for hash ``h`` (Knuth multiplicative
    hash -> [0, 1) -> PLACE_WEIGHTS bucket)."""
    x = (h * 2654435761 % 2**32) / 2**32
    acc = 0.0
    for k, w in enumerate(PLACE_WEIGHTS):
        acc += w
        if x < acc:
            return k
    return len(PLACE_WEIGHTS) - 1


def snapshot_problem(trace: Sequence[TraceJobSpec], t_s: float) -> Problem:
    """The fill problem of the trace's active set at ``t_s``.

    Placement is deterministic (no scheduler in the loop — this benchmarks
    the rate solve, not placement): each task lands on a weighted-hash host
    of its job's rack; cross-rack jobs alternate racks per task and their
    flows traverse the trunk."""
    demands: List[float] = []
    paths: List[Tuple[str, ...]] = []
    for ji in active_jobs_at(trace, t_s):
        spec = trace[ji]
        bw = float(MODEL_FLEET[spec.model]["bw_gbps"])
        cross = (ji % CROSS_RACK_MOD == 0)
        for k in range(spec.n_tasks):
            rack = (ji + (k % 2 if cross else 0)) % 2
            host = f"h{rack}{_pick_host(ji * 31 + k)}"
            paths.append((host, "trunk") if cross else (host,))
            demands.append(bw)
    caps = {f"h{r}{k}": NIC_TIERS[k] for r in range(2)
            for k in range(len(NIC_TIERS))}
    caps["trunk"] = TRUNK_GBPS
    return demands, paths, caps


def run() -> None:
    n_jobs = common.pick(10_000, 300)
    n_snapshots = common.pick(1024, 16)
    trace = generate_production_trace(MODEL_FLEET, n_jobs=n_jobs, seed=7)
    horizon = max(s.submit_time_s for s in trace)
    times = [horizon * (i + 0.5) / n_snapshots for i in range(n_snapshots)]
    probs = [snapshot_problem(trace, t) for t in times]
    probs = [p for p in probs if p[0]]  # drop empty off-peak snapshots
    mats = [fluid.problem_matrix(d, p, c)[:3] for d, p, c in probs]
    n_flows = sum(len(p[0]) for p in probs)

    # oracle: sequential per-snapshot python fills; best of 2 passes so a
    # background hiccup doesn't flatter the vectorized speedups
    py_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        golden = [fluid.fill_python(np.asarray(d, dtype=float), p, c)
                  for d, p, c in probs]
        py_s = min(py_s, time.perf_counter() - t0)
    record_trace_row(name="trace_fill_python", backend="python",
                     n_jobs=n_jobs, n_problems=len(probs), n_flows=n_flows,
                     seconds=py_s, problems_per_s=len(probs) / py_s,
                     flows_per_s=n_flows / py_s, speedup_vs_python=1.0,
                     max_abs_err_vs_python=0.0)
    emit("trace_fill_python", py_s * 1e6 / len(probs),
         f"n_jobs={n_jobs};n_problems={len(probs)};n_flows={n_flows}")

    for backend in ("jnp", "kernel") if common.on_tpu() else ("jnp",):
        rates = fluid.fill_corpus(mats, backend=backend)  # warmup (jit)
        best = float("inf")
        for _ in range(common.pick(5, 1)):
            t0 = time.perf_counter()
            rates = fluid.fill_corpus(mats, backend=backend)
            best = min(best, time.perf_counter() - t0)
        err = max(float(np.max(np.abs(r - g))) if len(g) else 0.0
                  for r, g in zip(rates, golden))
        record_trace_row(name=f"trace_fill_{backend}", backend=backend,
                         n_jobs=n_jobs, n_problems=len(probs),
                         n_flows=n_flows, seconds=best,
                         problems_per_s=len(probs) / best,
                         flows_per_s=n_flows / best,
                         speedup_vs_python=py_s / best,
                         max_abs_err_vs_python=err)
        emit(f"trace_fill_{backend}", best * 1e6 / len(probs),
             f"speedup={py_s / best:.1f}x;max_abs_err={err:.3g}")
