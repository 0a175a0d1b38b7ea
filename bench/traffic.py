"""The one traffic generator: a job stream for a cell, made from a mix file.

A mix file (``bench/traffic/<name>.json``) holds parameters only.  Every
mix goes through :func:`job_stream`:

1. A trace is drawn from the mix's fixed ``population_seed`` by the
   generator that ``trace.generator`` names: ``gavel`` (the paper's
   Sec. IV-A traces: stationary Poisson arrivals sized to a target load of
   the cluster's GPUs, uniform durations, Bernoulli priorities) or
   ``production`` (diurnal Poisson arrivals, lognormal durations, the
   fleet's task counts times a multiplier).  Both are the generators of
   the program's ``core/trace.py`` copied here, so that the yardstick
   cannot move with the program.
2. ``cut_at_s`` (optional) cuts the trace at that second: the jobs live
   then are submitted at t = 0 with what is left of their durations, and
   later arrivals keep their offsets from the cut.  ``horizon_s`` drops
   arrivals after it.
3. Where the mix gives ``shuffle_block``, the run's ``--seed`` permutes
   the jobs inside consecutive blocks of that many arrivals: each block
   keeps its arrival times and its set of jobs, and the seed decides which
   job comes at which time.  Without it the stream is the same for every
   seed, and the seed only lists the cluster's alike workers in another
   order (``harness.make_cluster``).
4. ``time_scale`` compresses trace time into simulated time.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class JobSpec:
    model: str
    submit_s: float       # trace seconds, before time_scale
    duration_s: float     # trace seconds, before time_scale
    high_priority: bool
    n_tasks: int


def gavel_trace(fleet: Dict[str, dict], *, duration_s: float, seed: int,
                total_gpus: int, target_load: float,
                high_priority_frac: float = 0.4,
                job_duration_range_s: Sequence[float] = (1800.0, 5400.0),
                ) -> List[JobSpec]:
    """Poisson arrivals at the rate that keeps ``target_load`` of
    ``total_gpus`` busy on average, uniform durations, Bernoulli
    priorities; deterministic in ``seed``."""
    rng = np.random.default_rng(seed)
    names = sorted(fleet)
    mean_tasks = float(np.mean([fleet[m].get("n_tasks", 2) for m in names]))
    mean_dur = float(np.mean(job_duration_range_s))
    rate = target_load * total_gpus / (mean_tasks * mean_dur)
    jobs: List[JobSpec] = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / rate))
        if t >= duration_s:
            return jobs
        model = names[int(rng.integers(len(names)))]
        dur = float(rng.uniform(*job_duration_range_s))
        high = bool(rng.random() < high_priority_frac)
        jobs.append(JobSpec(model=model, submit_s=t, duration_s=dur,
                            high_priority=high,
                            n_tasks=int(fleet[model].get("n_tasks", 2))))


def production_trace(fleet: Dict[str, dict], *, n_jobs: int,
                     duration_s: float = 24 * 3600.0, seed: int,
                     diurnal_amplitude: float = 0.6, peak_hour: float = 14.0,
                     day_s: float = 24 * 3600.0,
                     median_duration_s: float = 1200.0,
                     duration_sigma: float = 1.2,
                     duration_clip_s: Sequence[float] = (60.0, 6 * 3600.0),
                     high_priority_frac: float = 0.3,
                     task_multipliers: Sequence[int] = (1, 2, 4),
                     task_weights: Sequence[float] = (0.7, 0.2, 0.1),
                     ) -> List[JobSpec]:
    """Diurnal nonhomogeneous-Poisson arrivals by thinning, lognormal
    durations clipped to ``duration_clip_s``, task counts of the fleet's
    model times a weighted multiplier, Bernoulli priorities; sorted by
    submit time and deterministic in ``seed``."""
    rng = np.random.default_rng(seed)
    names = sorted(fleet)
    amp = min(max(float(diurnal_amplitude), 0.0), 1.0)
    base_rate = n_jobs / duration_s
    lam_max = base_rate * (1.0 + amp)
    peak_s = peak_hour * 3600.0
    lo, hi = duration_clip_s
    weights = np.asarray(task_weights, dtype=float)
    weights = weights / weights.sum()
    jobs: List[JobSpec] = []
    t = 0.0
    while len(jobs) < n_jobs:
        t += float(rng.exponential(1.0 / lam_max))
        if t >= duration_s:
            duration_s += day_s
        lam_t = base_rate * (
            1.0 + amp * np.cos(2.0 * np.pi * (t - peak_s) / day_s))
        if rng.random() * lam_max > lam_t:
            continue
        model = names[int(rng.integers(len(names)))]
        dur = float(np.clip(
            median_duration_s * np.exp(duration_sigma * rng.standard_normal()),
            lo, hi))
        mult = int(rng.choice(np.asarray(task_multipliers), p=weights))
        jobs.append(JobSpec(
            model=model, submit_s=t, duration_s=dur,
            high_priority=bool(rng.random() < high_priority_frac),
            n_tasks=int(fleet[model].get("n_tasks", 2)) * mult))
    return jobs


GENERATORS = {"gavel": gavel_trace, "production": production_trace}


def cut(jobs: Sequence[JobSpec], at_s: float) -> List[JobSpec]:
    """The trace as seen from ``at_s``: live jobs at t = 0 with their
    remaining durations, later arrivals shifted by ``-at_s``."""
    out = []
    for j in jobs:
        end = j.submit_s + j.duration_s
        if j.submit_s <= at_s < end:
            out.append(dataclasses.replace(j, submit_s=0.0,
                                           duration_s=end - at_s))
        elif j.submit_s > at_s:
            out.append(dataclasses.replace(j, submit_s=j.submit_s - at_s))
    return out


def shuffle_blocks(jobs: Sequence[JobSpec], block: int,
                   seed: int) -> List[JobSpec]:
    """Permute which job takes which arrival time inside each block of
    ``block`` consecutive arrivals; the arrival times stay in place."""
    rng = random.Random(seed)
    out: List[JobSpec] = []
    for s in range(0, len(jobs), block):
        part = list(jobs[s:s + block])
        times = [j.submit_s for j in part]
        rng.shuffle(part)
        out.extend(dataclasses.replace(j, submit_s=t)
                   for j, t in zip(part, times))
    return out


def job_stream(mix: dict, fleet: Dict[str, dict],
               seed: int) -> List[JobSpec]:
    """The cell's jobs for run seed ``seed``, in arrival order."""
    params = dict(mix["trace"])
    generator = GENERATORS[params.pop("generator", "production")]
    jobs = generator(fleet, seed=int(mix["population_seed"]), **params)
    at = mix.get("cut_at_s")
    if at is not None:
        jobs = cut(jobs, float(at))
    horizon: Optional[float] = mix.get("horizon_s")
    if horizon is not None:
        jobs = [j for j in jobs if j.submit_s <= horizon]
    if "shuffle_block" in mix:
        return shuffle_blocks(jobs, int(mix["shuffle_block"]), seed)
    return jobs


def horizon_ms(jobs: Sequence[JobSpec], time_scale: float) -> float:
    """Simulated ms at which the last job departs."""
    return max((j.submit_s + j.duration_s for j in jobs),
               default=0.0) * time_scale * 1e3

