"""The control of the ``correct`` comparison, and the faults it must catch.

The control puts the plain reference fill in the program's place,
computed in bfloat16, the precision below the float32 that the
configurations state for the rate solve.  A sound comparison reads it as
not correct.

:func:`installed` patches one of these into the program for the length of
a ``with`` block:

- ``control``: the bfloat16 fill in place of the fill kernel;
- ``frozen``: a chunk that returns without advancing simulated time (a
  step that returns its state unchanged);
- ``half_batch``: the fill leaves every second real problem of a batch
  unsolved, at zero;
- ``altered_rate``: one rate of every fill batch is altered by 1% where
  it is produced;
- ``altered_placement``: each admitted job's first pod is moved onto
  its second pod's node;
- ``slow_compute``: the event loop runs every compute phase 1% long;
- ``skipped_step``: the event loop leaves one due job in 50 unstepped
  until its next tick;
- ``shifted_start``: under a controller, an aligned job starts 1 ms after
  its circle offset asks;
- ``dropped_pause``: one stop-and-wait realign in 20 is skipped;
- ``dropped_inject``: compute phases leave out the controller's injected
  idle;
- ``worse_node``: the Metronome plugin's best-scored node of the first pod
  with a choice is scored below every other, so the pod goes to a
  Filter-passing node the reference ranks lower;
- ``false_refusal``: every fourth admission attempt is refused outright;
- ``shifted_offset``: the job admitted last is aligned one circle slot
  (base / Di-Pre) after the offset the controller worked out;
- ``unstretched_period``: a job whose circle injects idle is aligned on its
  own period instead of the stretched one.

``shifted_start``, ``dropped_pause`` and ``dropped_inject`` act in the
simulator after the controller has answered, so the answers the harness
records are the sound ones; the last four change what is recorded.

The benchmark's own runs never import this module; :func:`main` takes the
readings that the limits are set from.
"""
from __future__ import annotations

import contextlib

import numpy as np

EPS_BF16 = 1e-2


def fill_bf16(demands, routes, caps):
    """Progressive filling over a ``(B, F, L)`` batch in bfloat16 (JAX)."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    d = jnp.asarray(demands, bf)
    r = jnp.asarray(routes, bf)
    c = jnp.asarray(caps, bf)
    f = d.shape[1]
    big = jnp.asarray(1e30, jnp.float32).astype(bf)

    def cond(s):
        return jnp.logical_and(jnp.any(s[2] > 0), s[3] < f + 1)

    def body(s):
        rates, rem, act, i = s
        counts = jnp.einsum("bfl,bf->bl", r, act)
        ratio = jnp.where(counts > 0, rem / jnp.maximum(counts, 1), big)
        head = jnp.where(act > 0, d - rates, big)
        inc = jnp.maximum(jnp.minimum(ratio.min(axis=1), head.min(axis=1)),
                          0).astype(bf)
        rates = (rates + inc[:, None] * act).astype(bf)
        rem = (rem - inc[:, None] * counts).astype(bf)
        sat = (rem <= EPS_BF16).astype(bf)
        blocked = jnp.einsum("bfl,bl->bf", r, sat) > 0
        met = rates >= d - EPS_BF16
        act = jnp.where(jnp.logical_or(met, blocked), 0, act).astype(bf)
        return rates, rem, act, i + 1

    act0 = (d > EPS_BF16).astype(bf)
    out = jax.lax.while_loop(cond, body, (jnp.zeros_like(d), c, act0, 0))[0]
    return out.astype(jnp.float32)


_fill_bf16_jit = None


def _control_fill(demands, routes, caps, interpret=None):
    global _fill_bf16_jit
    if _fill_bf16_jit is None:
        import jax
        _fill_bf16_jit = jax.jit(fill_bf16)
    return np.asarray(_fill_bf16_jit(demands, routes, caps))


@contextlib.contextmanager
def installed(kind: str):
    """Patch the program with the control or one fault while the block
    runs."""
    from repro.core import simulator
    from repro.kernels import ops

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if kind == "control":
        patch(ops, "progressive_fill", _control_fill)
        patch(ops, "progressive_fill_ref",
              lambda d, r, c: _control_fill(d, r, c))
    elif kind in ("half_batch", "altered_rate"):
        for name in ("progressive_fill", "progressive_fill_ref"):
            patch(ops, name, _broken(getattr(ops, name), kind))
    elif kind == "slow_compute":
        enter = simulator.ClusterSimulator._enter_compute

        def slow(self, st, inject):
            enter(self, st, inject)
            st.phase_end += 0.01 * st.job.traffic.compute_ms
            self._sync_job(st)

        patch(simulator.ClusterSimulator, "_enter_compute", slow)
    elif kind == "skipped_step":
        step = simulator.ClusterSimulator._step_job
        calls = [0]

        def skipping(self, st):
            calls[0] += 1
            if calls[0] % 50:
                step(self, st)

        patch(simulator.ClusterSimulator, "_step_job", skipping)
    elif kind == "shifted_start":
        admit = simulator.ClusterSimulator._admit_job

        def late(self, job):
            admit(self, job)
            ctl = self.controller
            if ctl is not None and ctl.job_alignment(job.name) is not None:
                st = self.jobs[job.name]
                st.start_time += 1.0
                st.phase_end += 1.0
                self._sync_job(st)

        patch(simulator.ClusterSimulator, "_admit_job", late)
    elif kind == "dropped_pause":
        realign = simulator.ClusterSimulator._apply_realign
        calls = [0]

        def dropping(self, jname):
            calls[0] += 1
            if calls[0] % 20:
                realign(self, jname)

        patch(simulator.ClusterSimulator, "_apply_realign", dropping)
    elif kind == "dropped_inject":
        enter = simulator.ClusterSimulator._enter_compute

        def no_inject(self, st, inject):
            enter(self, st, 0.0)

        patch(simulator.ClusterSimulator, "_enter_compute", no_inject)
    elif kind == "worse_node":
        from repro.core.scheduler import MetronomePlugin
        score_nodes = MetronomePlugin.score_nodes

        done = [False]

        def demoted(self, ctx, cluster, pod, nodes, registry):
            out = score_nodes(self, ctx, cluster, pod, nodes, registry)
            if len(out) > 1 and not done[0]:
                done[0] = True
                top = max(out, key=lambda n: (out[n], -cluster.index(n)))
                out[top] = -1.0
            return out

        patch(MetronomePlugin, "score_nodes", demoted)
    elif kind == "false_refusal":
        from repro.core import framework
        schedule_workload = framework.SchedulingFramework.schedule_workload
        calls = [0]

        def refusing(self, wl):
            calls[0] += 1
            if calls[0] % 4 == 0:
                return False
            return schedule_workload(self, wl)

        patch(framework.SchedulingFramework, "schedule_workload", refusing)
    elif kind in ("shifted_offset", "unstretched_period"):
        from repro.core.controller import StopAndWaitController
        admit = simulator.ClusterSimulator._admit_job
        job_alignment = StopAndWaitController.job_alignment
        last, period = [None], {}

        def noting(self, job):
            last[0] = job.name
            period[job.name] = job.traffic.period_ms
            admit(self, job)

        def misaligned(self, job):
            a = job_alignment(self, job)
            if a is None:
                return a
            off, pe = a
            if kind == "unstretched_period":
                if self.injected_ms.get(job, 0.0) > 0 and job in period:
                    return off % period[job], period[job]
                return a
            if job != last[0]:
                return a
            base = next(st.scheme.base_ms for st in self.links.values()
                        if job in st.scheme.jobs)
            return (off + base / self.di_pre) % pe, pe

        patch(simulator.ClusterSimulator, "_admit_job", noting)
        patch(StopAndWaitController, "job_alignment", misaligned)
    elif kind == "frozen":
        run = simulator.ClusterSimulator.run

        def frozen(self):
            if getattr(self, "_frozen_once", False):
                return self._result()
            self._frozen_once = True
            return run(self)

        patch(simulator.ClusterSimulator, "run", frozen)
    elif kind == "altered_placement":
        from repro.core import framework
        schedule_job = framework.SchedulingFramework.schedule_job

        def moved(self, job):
            ok = schedule_job(self, job)
            if ok and len(job.tasks) > 1:
                job.tasks[0].node = job.tasks[1].node
            return ok

        patch(framework.SchedulingFramework, "schedule_job", moved)
    else:
        raise ValueError(f"unknown control or fault {kind!r}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def _broken(fn, kind: str):
    def wrapped(demands, routes, caps, *a, **kw):
        out = np.array(fn(demands, routes, caps, *a, **kw), copy=True)
        live = np.argwhere(np.asarray(demands) > 0)
        if kind == "half_batch":
            rows = np.unique(live[:, 0])
            out[rows[1::2]] = 0.0
        elif len(live):
            b, f = live[0]
            out[b, f] *= 1.01
        return out
    return wrapped


def main(argv=None) -> int:
    """Readings for the limits: runs of one cell on the chip, one per seed,
    in one process, sound or with the control or a fault installed; one
    JSON line each with the numbers compared.

        python3 bench/control.py --workload <cell> --seconds <s>
            [--kind control|<fault>] --seeds <n> [<n> ...]
    """
    import argparse
    import json
    import sys
    import time
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import harness
    from bench.spec import load_cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kind", default=None)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root / "BENCHMARK.json")
    for seed in args.seeds:
        t = time.perf_counter()
        ctx = (installed(args.kind) if args.kind
               else contextlib.nullcontext())
        with ctx:
            out = harness.run(cell, seed=seed, seconds=args.seconds,
                              trace=False, root=root, t_start=t,
                              rehearse=args.rehearse)
        print(json.dumps({"kind": args.kind or "sound", "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]
                          or out.get("rehearsal_metrics")}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
