"""The harness's own instruments, fitted onto one simulator instance.

Nothing here changes what the program computes: each probe wraps a call of
the live objects, times or counts it while the window is open, and keeps
what the checks need afterwards.

- admission: every ``ClusterSimulator._try_schedule`` call of the run
  (an attempt; retries from the pending queue and refusals included) with
  the time, the cluster state it started from and the placement and
  controller answers it left, and the window's calls counted.  The record
  (see :meth:`Probes._admission_state`) holds what an admission reference
  needs: free resources, link capacities, each incoming pod's demand and
  traffic, every live task, the jobs' priorities and submission times, the
  workload's dependencies, the latency between nodes, the policy and the
  Metronome plugin's Score constants, and the controller's answers;
- the stop-and-wait controller, where the policy has one: its answers in
  force (each live job's alignment and injected idle) after every attempt
  and every eviction, link change or traffic change it hears of, and the
  realigns each of its drift and phase-error reports asks for, with the
  time, for the progress reference to follow;
- every ``FluidEngine.solve_batch`` call: a count, non-finite answers, and
  a reservoir sample of its problems with their answers, drawn from the
  run's seed;
- bytes of the real fill problems handed to ``fluid.fill_many``;
- compilations (JAX's monitoring events) while the window is open.

Under ``--trace 1`` the admission and solve calls also run inside
``jax.profiler.TraceAnnotation`` spans, so that the device trace can say
what the host was doing in each idle gap.
"""
from __future__ import annotations

import contextlib
import functools
import random
from typing import Dict, List, Optional

import numpy as np

ADMIT_SPAN = "bench.admit"
SOLVE_SPAN = "bench.solve"
CHUNK_SPAN = "bench.chunk"

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traced",
    "/jax/core/compile/backend_compile_duration": "compiled",
}


class Probes:
    def __init__(self, sim, *, seed: int, sample_solves: int,
                 trace: bool, policy: Optional[dict] = None) -> None:
        self.sim = sim
        self.policy = policy
        self.on = False
        self.trace = trace
        self._rng = random.Random(seed)
        self.sample_size = int(sample_solves)
        # window counters
        self.admit_calls = 0
        self.admissions: List[dict] = []
        self.placed: Dict[str, List[str]] = {}
        # the controller's answers in force from ``t_ms`` on, and the
        # realigns its reports asked for
        self.control: List[dict] = []
        self.realigns: List[dict] = []
        self.solve_calls = 0
        self.solve_problems = 0
        self.failed = 0
        self.nonfinite = 0
        self.samples: List[tuple] = []
        self.fill_bytes = 0
        self.fill_calls = 0
        self.compiles = {"traced": 0, "compiled": 0}
        self._install()

    # ------------------------------------------------------------ plumbing
    def _span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _install(self) -> None:
        import jax.monitoring

        from repro.core import fluid

        sim = self.sim

        try_schedule = sim._try_schedule

        def recorded_try_schedule(wl):
            # every decision of the run is kept for the check, set-up's
            # included; only the window's are timed
            before = self._admission_state(wl)
            if not self.on:
                ok = try_schedule(wl)
            else:
                try:
                    with self._span(ADMIT_SPAN):
                        ok = try_schedule(wl)
                except Exception:
                    self.failed += 1
                    raise
                self.admit_calls += 1
            before["t_ms"] = sim.now
            before["job"] = wl.jobs[0].name
            before["admitted"] = bool(ok)
            before["placed"] = [t.node for t in wl.all_tasks()]
            before["control_after"] = self._record_control()
            self.admissions.append(before)
            if ok:
                self.placed[before["job"]] = before["placed"]
            return ok

        sim._try_schedule = recorded_try_schedule
        if sim.controller is not None:
            self._wrap_controller(sim.controller)

        engine = sim.fluid
        solve_batch = engine.solve_batch

        def counted_solve(problems):
            if not self.on:
                return solve_batch(problems)
            try:
                with self._span(SOLVE_SPAN):
                    out = solve_batch(problems)
            except Exception:
                self.failed += 1
                raise
            self.solve_calls += 1
            if not all(np.isfinite(r).all() for r in out):
                self.failed += 1
                self.nonfinite += 1
            # the engine builds each problem afresh and never writes an
            # answer again, so the sample keeps references
            for prob, rates in zip(problems, out):
                self._reservoir((*prob, rates))
            return out

        engine.solve_batch = counted_solve

        fill_many = fluid.fill_many

        def counted_fill_many(problems, **kw):
            if self.on:
                self.fill_calls += 1
                self.fill_bytes += fill_bytes(problems)
            return fill_many(problems, **kw)

        self._restore = [(fluid, "fill_many", fill_many)]
        fluid.fill_many = counted_fill_many

        def on_event(event: str, duration: float, **kw) -> None:
            kind = _COMPILE_EVENTS.get(event)
            if kind is not None and self.on:
                self.compiles[kind] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def _wrap_controller(self, ctl) -> None:
        """Record the controller's answers as the program gets them: the
        state in force after each call that can change it outside an
        admission attempt, and the realigns each report asks for."""
        sim = self.sim

        def changing(fn):
            def recorded(*a, **kw):
                out = fn(*a, **kw)
                self._record_control()
                return out
            return recorded

        def reporting(fn):
            def recorded(*a, **kw):
                actions = fn(*a, **kw)
                if actions:
                    self.realigns.append({"t_ms": sim.now,
                                          "jobs": [x.job for x in actions]})
                return actions
            return recorded

        for name in ("on_evict", "on_link_change", "report_traffic_change"):
            setattr(ctl, name, changing(getattr(ctl, name)))
        for name in ("report_iteration", "report_phase_error"):
            setattr(ctl, name, reporting(getattr(ctl, name)))

    def close(self) -> None:
        """Undo the module-level wrap (instance wraps die with the
        simulator)."""
        for mod, name, orig in self._restore:
            setattr(mod, name, orig)
        self.on = False

    # ---------------------------------------------------------- recorders
    def _reservoir(self, item: tuple) -> None:
        self.solve_problems += 1
        k = self.solve_problems
        if len(self.samples) < self.sample_size:
            self.samples.append(item)
        else:
            j = self._rng.randrange(k)
            if j < self.sample_size:
                self.samples[j] = item

    def control_state(self) -> Optional[dict]:
        """The controller's answers for every live job: ``align``, its
        ``(offset_ms, period_eff_ms)`` where ``job_alignment`` gives one,
        and ``inject``, its ``injected_ms`` where it has one; None without
        a controller."""
        ctl = self.sim.controller
        if ctl is None:
            return None
        align, inject = {}, {}
        for name in self.sim.framework.registry.jobs:
            a = ctl.job_alignment(name)
            if a is not None:
                align[name] = (float(a[0]), float(a[1]))
            if name in ctl.injected_ms:
                inject[name] = float(ctl.injected_ms[name])
        return {"align": align, "inject": inject}

    def _record_control(self) -> Optional[dict]:
        state = self.control_state()
        if state is not None:
            self.control.append({"t_ms": self.sim.now, "state": state})
        return state

    def _admission_state(self, wl) -> dict:
        """The cluster as attempt ``wl`` finds it.  ``pods`` are the
        workload's pods in ``wl.all_tasks()`` order (each with its job,
        priority, period, duty, demand, resources and spread); ``tasks``
        every live task in registry order, the order in which the program
        groups a link's jobs; ``submit_s`` each live and incoming job's
        submission time, which breaks Eq. 16's priority ties (earliest
        first); ``dependencies`` the workload's AppGroup job pairs;
        ``latency`` tau between every two nodes, in ``nodes`` order (the
        cluster's matrix, copied whole);
        ``score_params`` the Metronome plugin's constants (None under
        another plugin)."""
        cl = self.sim.framework.cluster
        reg = self.sim.framework.registry
        topo = cl.topology
        free, cap, alloc = {}, {}, {}
        for name in cl.node_names:
            node = cl.node(name)
            f, c = node.free, node.capacity
            free[name] = (f.cpu, f.mem, f.gpu)
            cap[name] = (c.cpu, c.mem, c.gpu)
            alloc[name] = node.alloc_bw
        leaf_of = dict(getattr(topo, "leaf_of", {}) or {})
        uplinks = {leaf_of[n]: topo.uplink_of(n).alloc_bw
                   for n in leaf_of if topo.uplink_of(n) is not None}
        submit = {name: job.submit_time_s for name, job in reg.jobs.items()}
        submit.update((job.name, job.submit_time_s) for job in wl.jobs)
        return {
            "nodes": list(cl.node_names), "free": free, "capacity": cap,
            "alloc_bw": alloc, "leaf_of": leaf_of, "uplink_alloc": uplinks,
            "pods": [{"req": (t.resources.cpu, t.resources.mem,
                              t.resources.gpu),
                      "bw": t.traffic.bw_gbps, "spread": t.spread,
                      "job": t.job, "priority": t.priority,
                      "period_ms": t.traffic.period_ms,
                      "duty": t.traffic.duty}
                     for t in wl.all_tasks()],
            "tasks": [{"job": t.job, "worker": t.node, "priority": t.priority,
                       "period_ms": t.traffic.period_ms,
                       "duty": t.traffic.duty, "bw_gbps": t.traffic.bw_gbps}
                      for t in reg.tasks.values()],
            "submit_s": submit,
            "dependencies": [list(pair) for pair in wl.dependencies],
            "latency": cl.latency.tolist(),
            "link_capacity": {l: cl.link_capacity(l) for l in cl.link_ids},
            "link_alloc": {l: cl.link_alloc(l) for l in cl.link_ids},
            "policy": self.policy,
            "score_params": self.score_params,
            "control_before": self.control_state(),
        }

    @functools.cached_property
    def score_params(self) -> Optional[dict]:
        """The Metronome plugin's Score constants as the live plugin holds
        them (read once: the plugin is fixed for the run); None under
        another plugin."""
        from repro.core.scheduler import MetronomePlugin

        plugin = self.sim.framework.plugin
        if not isinstance(plugin, MetronomePlugin):
            return None
        return {"di_pre": plugin.di_pre, "g_t_ms": plugin.g_t_ms,
                "e_t_frac": plugin.e_t_frac,
                "rotation_mode": plugin.rotation_mode, "joint": plugin.joint}


def fill_bytes(problems) -> int:
    """Bytes a fill must move for the real problems of one ``fill_many``
    call: each problem's demands, route matrix, link capacities and rates,
    unpadded, at 4 bytes each.  The zero-demand problems that pad a batch
    are not work and are skipped."""
    total = 0
    for demands, routes, caps in problems:
        if not np.any(demands):
            continue
        f = int(demands.shape[0])
        l = int(caps.shape[0])
        total += 4 * (f + f * l + l + f)
    return total
