"""Plain references that decide ``correct``.  Nothing here imports the
program: each function takes the inputs a decision was made from and
recomputes the answer the configuration's semantics call for.

- :func:`fill` -- max-min fair rates by progressive filling (float64): all
  unfrozen flows grow together, a flow freezes when its demand is met or a
  link on its path is full.
- :func:`follow` -- the fluid model of the configuration, followed event
  by event in float64 from the admissions, with the stop-and-wait
  arithmetic of a controller where the policy has one: the completion
  time of every iteration of every job.  :func:`progress_gap` compares it
  with the program's.

Admission references are files of their own, ``bench/admission/<name>.py``,
named by a configuration's ``check.admission``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

EPS = 1e-9


def fill(demands: Sequence[float], paths: Sequence[Sequence[str]],
         caps: Dict[str, float]) -> List[float]:
    """Progressive-filling max-min fair rates (float64)."""
    n = len(paths)
    d = [float(x) for x in demands]
    rem = {l: float(caps[l]) for p in paths for l in p}
    rates = [0.0] * n
    active = [i for i in range(n) if d[i] > EPS]
    while active:
        counts: Dict[str, int] = {}
        for i in active:
            for l in paths[i]:
                counts[l] = counts.get(l, 0) + 1
        inc = min(d[i] - rates[i] for i in active)
        if counts:
            inc = min(inc, min(rem[l] / c for l, c in counts.items()))
        inc = max(inc, 0.0)
        for i in active:
            rates[i] += inc
        for l, c in counts.items():
            rem[l] -= inc * c
        still = [i for i in active if rates[i] < d[i] - EPS
                 and all(rem[l] > EPS for l in paths[i])]
        if len(still) == len(active):
            break
        active = still
    return rates


# ----------------------------------------------------------------- progress
def links_of(layout: dict) -> Tuple[Dict[str, float], Dict[str, str]]:
    """(capacity of every link, leaf of every worker) of a configuration's
    cluster: one NIC per worker, named after it; with ``leaves``, one
    uplink per leaf (``uplink:<leaf>``) carrying its NICs over the
    oversubscription factor."""
    caps = {n["name"]: float(n["bw_gbps"]) for n in layout["nodes"]}
    leaf_of: Dict[str, str] = {}
    for leaf, members in layout.get("leaves", {}).items():
        for m in members:
            leaf_of[m] = leaf
        caps[f"uplink:{leaf}"] = (sum(caps[m] for m in members)
                                  / float(layout["oversubscription"]))
    return caps, leaf_of


def job_flows(nodes: Sequence[str], bw_gbps: float,
              leaf_of: Dict[str, str]) -> List[Tuple[float, Tuple[str, ...]]]:
    """(demand, path) of each flow of one communication phase: one flow
    per worker the job uses, at the summed demand of its pods there, over
    the worker's NIC and, when the job spans leaves, its leaf's uplink.  A
    job on one worker synchronises locally and has none."""
    used: Dict[str, int] = {}
    for n in nodes:
        used[n] = used.get(n, 0) + 1
    if len(used) <= 1:
        return []
    leaves = {leaf_of.get(n) for n in used}
    out = []
    for n, k in used.items():
        path = (n,)
        if len(leaves) > 1:
            path = (n, f"uplink:{leaf_of[n]}")
        out.append((bw_gbps * k, path))
    return out


def follow(jobs: Dict[str, dict], start: dict, admissions: Sequence[tuple],
           departures: Dict[str, float], layout: dict, until_ms: float,
           startup_ms: float = 0.0, control: Sequence[tuple] = (),
           realigns: Sequence[tuple] = ()) -> Dict[str, List[float]]:
    """Completion time (ms) of every iteration that ends from
    ``start["t_ms"]`` until ``until_ms``, per job.

    ``jobs`` maps a job to its ``compute_ms``, ``comm_ms``, per-pod
    ``bw_gbps`` and whether it is of ``high`` priority.  ``start`` is the
    state followed from: its time; per job then admitted, its ``workers``,
    ``phase`` (``waiting``, ``compute``, ``paused`` or ``comm``), the end
    of a timed phase (``end``, None while flows move), the Gb ``left`` on
    the flow of each worker, a realign ``pending`` for its next compute
    phase and a ``pause`` due there; and the controller's answers then in
    force (``control``, None without a controller).  ``admissions`` holds
    ``(time_ms, job, workers[, answers after it])`` of every later
    admission in order; ``departures`` the time each job leaves.

    A job admitted at t starts at t + ``startup_ms``, computes for its
    compute time, then moves ``demand x comm time`` over each of its flows
    at the max-min fair rates of all flows then active; the iteration ends
    when its last flow ends, and the next begins at once.

    Under a stop-and-wait controller the answers are timed inputs:
    ``control`` holds ``(time_ms, answers)`` in force from then on, and
    ``realigns`` ``(time_ms, jobs)`` that its drift reports asked for.
    Answers are ``{"align": {job: (offset_ms, period_eff_ms)}, "inject":
    {job: ms}}``.  The follower applies the paper's arithmetic itself: an
    aligned job's start waits until its first comm phase lands on
    ``offset (mod period_eff)``; every compute phase lasts ``compute_ms``
    plus the job's injected idle; after an admission every other live
    low-priority job realigns, as does each job a report names: in compute
    (or paused) its phase end moves on to the next time ``t = offset (mod
    period_eff)`` and it is paused; otherwise the realign waits for the
    start of its next compute phase, which then ends at such a time."""
    caps, leaf_of = links_of(layout)
    adm = sorted(admissions, key=lambda a: a[0])
    dep = sorted((t, name) for name, t in departures.items())
    ctl = sorted(control, key=lambda c: c[0])
    ral = sorted(realigns, key=lambda r: r[0])
    answers = start.get("control")
    t = float(start["t_ms"])
    live: Dict[str, dict] = {}
    flows: List[list] = []          # [job, demand, remaining Gb, path]
    out: Dict[str, List[float]] = {}
    for name, st in start["jobs"].items():
        spec = jobs[name]
        live[name] = {"phase": st["phase"], "end": st["end"],
                      "flows": job_flows(st["workers"], spec["bw_gbps"],
                                         leaf_of),
                      "pending": st.get("pending", False),
                      "pause": st.get("pause", 0.0)}
        out[name] = []
        for demand, path in live[name]["flows"]:
            left = st["left"].get(path[0], 0.0)
            if st["phase"] == "comm" and left > EPS:
                flows.append([name, demand, left, path])

    def realign(name: str, given: Optional[dict]) -> None:
        st = live.get(name)
        align = None if given is None else given["align"].get(name)
        if st is None or align is None:
            return
        offset, period = align
        if st["phase"] in ("compute", "paused"):
            st["end"] += (offset - (st["end"] % period)) % period
            st["phase"] = "paused"
        else:
            st["pending"] = True

    def enter_compute(name: str, st: dict) -> None:
        dur = jobs[name]["compute_ms"]
        if answers is not None:
            dur += answers["inject"].get(name, 0.0)
        dur += st["pause"]
        st["pause"] = 0.0
        if st["pending"]:
            align = None if answers is None else answers["align"].get(name)
            if align is not None:
                offset, period = align
                dur += (offset - ((t + dur) % period)) % period
            st["pending"] = False
        st.update(phase="compute", end=t + dur)

    rates: List[float] = [0.0] * len(flows)
    ai = di = ci = ri = 0
    dirty = True
    while True:
        if dirty:
            rates = fill([f[1] for f in flows], [f[3] for f in flows], caps)
            dirty = False
        nxt = until_ms
        if ai < len(adm):
            nxt = min(nxt, adm[ai][0])
        if di < len(dep):
            nxt = min(nxt, dep[di][0])
        if ri < len(ral):
            nxt = min(nxt, ral[ri][0])
        for st in live.values():
            if st["end"] is not None:
                nxt = min(nxt, st["end"])
        for f, r in zip(flows, rates):
            if r > EPS:
                nxt = min(nxt, t + f[2] / r * 1e3)
        nxt = max(nxt, t)
        dt = nxt - t
        if dt > 0:
            for f, r in zip(flows, rates):
                f[2] -= min(f[2], r * dt / 1e3)
        t = nxt
        if t >= until_ms:
            return out
        while ci < len(ctl) and ctl[ci][0] <= t + EPS:
            answers = ctl[ci][1]
            ci += 1
        while di < len(dep) and dep[di][0] <= t + EPS:
            name = dep[di][1]
            di += 1
            if live.pop(name, None) is not None:
                kept = [(f, r) for f, r in zip(flows, rates) if f[0] != name]
                flows = [f for f, _ in kept]
                rates = [r for _, r in kept]
                dirty = True
        while ai < len(adm) and adm[ai][0] <= t + EPS:
            name, workers = adm[ai][1], adm[ai][2]
            after = adm[ai][3] if len(adm[ai]) > 3 else None
            ai += 1
            begin = t + startup_ms
            align = None if after is None else after["align"].get(name)
            if align is not None:
                offset, period = align
                first_comm = (begin + jobs[name]["compute_ms"]
                              + after["inject"].get(name, 0.0))
                begin += (offset - first_comm) % period
            live[name] = {"phase": "waiting", "end": begin,
                          "flows": job_flows(workers, jobs[name]["bw_gbps"],
                                             leaf_of),
                          "pending": False, "pause": 0.0}
            out[name] = []
            if after is not None:
                for other in live:
                    if other != name and not jobs[other].get("high"):
                        realign(other, after)
        while ri < len(ral) and ral[ri][0] <= t + EPS:
            for name in ral[ri][1]:
                realign(name, answers)
            ri += 1
        if any(f[2] <= EPS for f in flows):
            kept = [(f, r) for f, r in zip(flows, rates) if f[2] > EPS]
            flows = [f for f, _ in kept]
            rates = [r for _, r in kept]
            dirty = True
        busy = {f[0] for f in flows}
        for name, st in live.items():
            spec = jobs[name]
            due = st["end"] is not None and t + EPS >= st["end"]
            if st["phase"] == "waiting" and due:
                enter_compute(name, st)
            elif st["phase"] in ("compute", "paused") and due:
                if st["flows"]:
                    for demand, path in st["flows"]:
                        flows.append([name, demand,
                                      demand * spec["comm_ms"] / 1e3, path])
                        rates.append(0.0)
                    st.update(phase="comm", end=None)
                    dirty = True
                else:
                    st.update(phase="comm", end=t + spec["comm_ms"])
            elif st["phase"] == "comm" and (
                    due or (st["end"] is None and name not in busy)):
                out[name].append(t)
                enter_compute(name, st)


def progress_gap(program: Dict[str, List[float]],
                 reference: Dict[str, List[float]], until_ms: float) -> float:
    """Widest gap (ms) between the program's and the reference's
    completion time of one iteration, over every job.  Where one side
    completed an iteration that the other did not by ``until_ms``, the gap
    is at least the time from that completion to ``until_ms``."""
    gap = 0.0
    for name in set(program) | set(reference):
        a, b = program.get(name, []), reference.get(name, [])
        k = min(len(a), len(b))
        for x, y in zip(a[:k], b[:k]):
            gap = max(gap, abs(x - y))
        for x in a[k:] + b[k:]:
            gap = max(gap, until_ms - x)
    return gap
