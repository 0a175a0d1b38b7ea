"""Shared helpers for the per-table benchmarks.

Benches run their experiment grids through the Scenario/Policy sweep API
(``repro.core.experiment``).  Every sweep executed via :func:`run_sweep` is
recorded in-process; ``benchmarks/run.py --sweep-out`` persists the merged
record as schema-versioned ``BENCH_sweep.json`` (uploaded + validated in
CI), so the perf/result trajectory of every bench is a machine-readable
artifact instead of stdout-only CSV rows.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

from repro.configs.metronome_testbed import snapshot_scenario
from repro.core.experiment import Policy, Scenario, sweep
from repro.core.results import (SweepResult, to_bench_dict,
                                to_dynamic_throughput_dict,
                                to_robustness_dict, to_timing_dict,
                                to_trace_throughput_dict)
from repro.core.simulator import SimConfig

SCHEDULER_NAMES = ("metronome", "default", "diktyo", "ideal")
POLICIES = tuple(Policy(scheduler=s) for s in SCHEDULER_NAMES)

BENCH_CFG = SimConfig(duration_ms=150_000.0, seed=3, jitter_std=0.01)

# --smoke mode (benchmarks/run.py --smoke, exercised by CI): every bench
# runs end-to-end with tiny iteration counts / durations so the scripts
# cannot rot silently.  The flag is set BEFORE any run() executes; benches
# read it at call time via pick().
SMOKE = False

# guards every RECORDED_* recorder below: benches running cells on a
# thread pool (run.py --workers N) record from worker threads, and the
# emit()/record_*_row() read-modify-write patterns interleave without it
_RECORD_LOCK = threading.Lock()

# every sweep any bench ran this process (run.py --sweep-out persists it)
RECORDED_SWEEPS: List[SweepResult] = []

# every emit() row any bench printed this process (run.py --bench-out
# persists the merged record as schema-versioned BENCH_sched_time.json);
# CURRENT_ORIGIN is maintained by run.py around each bench module
RECORDED_EMITS: List[Dict[str, object]] = []
CURRENT_ORIGIN = ""

# every trace-throughput row bench_trace_throughput recorded this process
# (run.py --trace-out persists the merged record as schema-versioned
# BENCH_trace_throughput.json)
RECORDED_TRACE_ROWS: List[Dict[str, object]] = []

# every dynamic-throughput row bench_dynamic_throughput recorded this
# process (run.py --dynamic-out persists the merged record as
# schema-versioned BENCH_dynamic_throughput.json)
RECORDED_DYNAMIC_ROWS: List[Dict[str, object]] = []

# every graceful-degradation row bench_robustness recorded this process
# (run.py --robustness-out persists the merged record as schema-versioned
# BENCH_robustness.json)
RECORDED_ROBUSTNESS_ROWS: List[Dict[str, object]] = []

# parallel sweep execution (run.py --workers / --worker-mode): run_sweep
# fans independent grid cells over a thread or process pool; 1/thread =
# the historical serial path
WORKERS = 1
WORKER_MODE = "thread"

# content-keyed sweep cache (run.py --cache-dir, the nightly CI job):
# run_sweep consults/updates it when set; None = always compute
CACHE_DIR: Optional[str] = None


def pick(default, smoke_value):
    """``default`` normally, ``smoke_value`` under ``run.py --smoke``."""
    return smoke_value if SMOKE else default


def on_tpu() -> bool:
    """Whether JAX runs on a TPU.  Rows of the ``'kernel'`` backend time the
    compiled Pallas kernels and exist only there; elsewhere they are left
    out, never filled in by another path."""
    import jax  # deferred: most benches never touch the device
    return jax.devices()[0].platform == "tpu"


def bench_cfg(**overrides) -> SimConfig:
    """The standard bench SimConfig, smoke-shrunk when --smoke is active."""
    cfg = SimConfig(duration_ms=pick(150_000.0, 15_000.0), seed=3,
                    jitter_std=0.01)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def run_sweep(scenarios: Sequence[Scenario], policies: Sequence[Policy],
              cfg: Optional[SimConfig] = None, *, origin: str,
              strict: bool = True) -> SweepResult:
    """Run a grid through ``experiment.sweep`` and record it for the
    ``BENCH_sweep.json`` artifact.

    ``strict=True`` (the bench default) re-raises after recording if any
    cell failed, so a broken bench still fails run.py loudly — the
    isolation lives in the artifact, which keeps the healthy cells.

    With ``CACHE_DIR`` set (run.py --cache-dir, the nightly CI job) the
    grid is keyed on its *materialized content* (``benchmarks.cache``) and
    an unchanged grid is restored from disk instead of re-simulated."""
    key = None
    if CACHE_DIR is not None:
        from . import cache as _cache

        key = "sweep-" + _cache.fingerprint_grid(scenarios, policies, cfg)
        hit = _cache.load(CACHE_DIR, key)
        if hit is not None:
            hit.meta.update(origin=origin, smoke=SMOKE, cache="hit")
            with _RECORD_LOCK:
                RECORDED_SWEEPS.append(hit)
            if strict and hit.errors:
                bad = ", ".join(f"({c.scenario}, {c.policy})"
                                for c in hit.errors)
                raise RuntimeError(f"sweep cells failed in {origin}: {bad}")
            return hit
    sw = sweep(scenarios, policies, cfg, workers=WORKERS, mode=WORKER_MODE)
    sw.meta.update(origin=origin, smoke=SMOKE, workers=WORKERS)
    if key is not None and not sw.errors:
        from . import cache as _cache

        sw.meta.update(cache="miss")
        _cache.store(CACHE_DIR, key, sw)
    with _RECORD_LOCK:
        RECORDED_SWEEPS.append(sw)
    if strict and sw.errors:
        bad = ", ".join(f"({c.scenario}, {c.policy})" for c in sw.errors)
        for c in sw.errors:
            print(c.error, file=sys.stderr)
        raise RuntimeError(f"sweep cells failed in {origin}: {bad}")
    return sw


def snapshot_sweep(sid: str, n_iterations: Optional[int] = None,
                   cfg: Optional[SimConfig] = None,
                   policies: Sequence[Policy] = POLICIES, *,
                   origin: str) -> SweepResult:
    """One snapshot under every policy (each cell re-materializes the
    snapshot, so runs never share mutated Job objects).  The old
    ``run_snapshot_all`` dict — and its ``"_workloads"`` magic key — is
    replaced by the typed :class:`SweepResult` (priority splits live on
    each :class:`ExperimentResult`)."""
    if n_iterations is None:
        n_iterations = pick(400, 30)
    if cfg is None:
        cfg = bench_cfg()
    scn = snapshot_scenario(sid, n_iterations=n_iterations)
    return run_sweep([scn], policies, cfg, origin=origin)


def write_sweeps(path: str) -> None:
    """Persist every recorded sweep as schema-versioned BENCH_sweep.json."""
    import json

    with open(path, "w") as f:
        json.dump(to_bench_dict(RECORDED_SWEEPS, smoke=SMOKE), f, indent=1,
                  allow_nan=False)


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The harness contract: ``name,us_per_call,derived`` CSV rows, also
    recorded in-process for the BENCH_sched_time.json timing artifact."""
    print(f"{name},{us_per_call:.1f},{derived}")
    with _RECORD_LOCK:
        RECORDED_EMITS.append(
            {"name": name, "us_per_call": float(us_per_call),
             "derived": derived, "origin": CURRENT_ORIGIN})


def write_timings(path: str) -> None:
    """Persist every recorded emit() row as schema-versioned timing JSON
    (the BENCH_sched_time.json trajectory artifact)."""
    import json

    with open(path, "w") as f:
        json.dump(to_timing_dict(RECORDED_EMITS, smoke=SMOKE), f, indent=1,
                  allow_nan=False)


def record_trace_row(**row: object) -> None:
    """Record one trace-throughput row (see
    ``results.to_trace_throughput_dict`` for the field contract); run.py
    ``--trace-out`` persists the merged record."""
    row.setdefault("origin", CURRENT_ORIGIN)
    with _RECORD_LOCK:
        RECORDED_TRACE_ROWS.append(row)


def write_trace_throughput(path: str) -> None:
    """Persist every recorded trace-throughput row as schema-versioned
    JSON (the BENCH_trace_throughput.json artifact)."""
    import json

    with open(path, "w") as f:
        json.dump(to_trace_throughput_dict(RECORDED_TRACE_ROWS, smoke=SMOKE),
                  f, indent=1, allow_nan=False)


def record_dynamic_row(**row: object) -> None:
    """Record one dynamic-throughput row (see
    ``results.to_dynamic_throughput_dict`` for the field contract); run.py
    ``--dynamic-out`` persists the merged record."""
    row.setdefault("origin", CURRENT_ORIGIN)
    with _RECORD_LOCK:
        RECORDED_DYNAMIC_ROWS.append(row)


def write_dynamic_throughput(path: str) -> None:
    """Persist every recorded dynamic-throughput row as schema-versioned
    JSON (the BENCH_dynamic_throughput.json artifact)."""
    import json

    with open(path, "w") as f:
        json.dump(
            to_dynamic_throughput_dict(RECORDED_DYNAMIC_ROWS, smoke=SMOKE),
            f, indent=1, allow_nan=False)


def record_robustness_row(**row: object) -> None:
    """Record one graceful-degradation row (see
    ``results.to_robustness_dict`` for the field contract); run.py
    ``--robustness-out`` persists the merged record."""
    row.setdefault("origin", CURRENT_ORIGIN)
    with _RECORD_LOCK:
        RECORDED_ROBUSTNESS_ROWS.append(row)


def write_robustness(path: str) -> None:
    """Persist every recorded graceful-degradation row as schema-versioned
    JSON (the BENCH_robustness.json artifact)."""
    import json

    with open(path, "w") as f:
        json.dump(to_robustness_dict(RECORDED_ROBUSTNESS_ROWS, smoke=SMOKE),
                  f, indent=1, allow_nan=False)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.us = (time.perf_counter() - self.t0) * 1e6
