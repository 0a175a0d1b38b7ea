"""Metronome's admission (arXiv 2510.12274 Sec. III-B, Algorithm 1) as a
plain float64 reference that imports nothing of the program.

A configuration names it as ``"check": {"admission": "metronome"}``;
:func:`mismatch` judges one attempt's record (see ``bench/probes.py``).
For each pod of the attempt, in order, on the cluster as the program had
it with that attempt's earlier pods where the program put them:

- Filter: the spread cap (PodTopologySpread), Eq. 13 (CPU, memory, GPU
  within the node's free resources), Eq. 14 (the pod's demand within the
  NIC's allocatable bandwidth, and within the leaf uplink's where the
  placement makes the job span leaves).
- Score: Eq. 18 on every link the placement makes the job traverse: the
  link's jobs (LowComm pods left out), periods unified on one circle
  (G_T, E_T; see :func:`unify`), patterns on the Di-Pre circle, Eq. 15
  ranges with the Eq. 16 reference pinned, the Eq. 18 optimum found by
  enumerating the combinations in order.  Where the candidate closes a
  cross-link dependency loop through the pod's job (Cassini's affinity
  loop), the links of its affinity component are planned together.
- NormalizeScore: Eq. 19 among the best candidates, then the lowest node
  index; all or nothing over the attempt's pods.

Under a stop-and-wait controller it also judges the answers in force after
the attempt (see :func:`control_faults`).  The record's ``score_params``
must be the constants below.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

# the paper's constants (Sec. IV-A: Di-Pre 72 after Cassini; Sec. III-B:
# G_T and E_T); ``joint`` is the system's fabric-wide planner, on by default
PARAMS = {"di_pre": 72, "g_t_ms": 5.0, "e_t_frac": 0.10,
          "rotation_mode": "intermediate", "joint": True}
# the high one of the two priority classes (Sec. III-B; 0 is low), as the
# record gives them
HIGH = 1
MAX_COMBOS = 1 << 22
# the longest circle, in periods of its reference (the paper sets no bound)
MAX_BASE_MUL = 16
PERFECT = 100.0
EPS = 1e-9            # perfect within this of 100; scores within it tie
PERIOD_TOL_MS = 1e-9  # an answered period against the circle's
REL_TOL_MS = 1e-6     # two links' shifts of one job pair agree within this
# departure from the paper: a candidate whose placement makes the job cross
# a spine uplink scores this much lower (a rack-locality preference)
RACK_PENALTY = 0.5
CHUNK = 1 << 15     # combinations scored in one block


class TooLarge(RuntimeError):
    """A rotation problem beyond MAX_COMBOS: never skipped or sampled."""


# ----------------------------------------------------------------- the state
class State:
    """The cluster one pod of an attempt is scheduled on: the live tasks in
    registry order (each ``{"job", "node", "bw", "period", "duty"}``), the
    jobs' priority and submission time, and the record's links."""

    def __init__(self, rec: dict) -> None:
        self.rec = rec
        self.nodes: List[str] = list(rec["nodes"])
        self.index = {n: i for i, n in enumerate(self.nodes)}
        self.leaf_of: Dict[str, str] = dict(rec["leaf_of"])
        self.uplinks = {leaf: f"uplink:{leaf}" for leaf in rec["uplink_alloc"]}
        self.link_alloc: Dict[str, float] = dict(rec["link_alloc"])
        self.links = self.nodes + [l for l in self.link_alloc
                                   if l not in self.index]
        self.tasks = [{"job": t["job"], "node": t["worker"],
                       "bw": t["bw_gbps"], "period": t["period_ms"],
                       "duty": t["duty"]} for t in rec["tasks"]]
        self.prio = {t["job"]: t["priority"] for t in rec["tasks"]}
        self.prio.update((p["job"], p["priority"]) for p in rec["pods"])
        self.submit = dict(rec["submit_s"])
        self.free = {n: list(rec["free"][n]) for n in self.nodes}

    def order(self, jobs) -> List[str]:
        """Eq. 16: highest priority first, ties to the earliest submitted
        (then the name); index 0 is the pinned reference."""
        return sorted(jobs, key=lambda j: (-self.prio.get(j, 0),
                                           self.submit.get(j, 0.0), j))

    def place(self, pod: dict, node: str) -> None:
        self.tasks.append(pod_task(pod, node))
        self.free[node] = [f - r for f, r in zip(self.free[node], pod["req"])]


def pod_task(pod: dict, node: str) -> dict:
    return {"job": pod["job"], "node": node, "bw": pod["bw"],
            "period": pod["period_ms"], "duty": pod["duty"]}


def low_comm(t: dict) -> bool:
    """A LowComm pod declares no bandwidth need (Sec. III-B)."""
    return t["bw"] <= 0.0 or t["duty"] <= 0.0


# ------------------------------------------------------------ link demand
def spans(state: State, nodes) -> bool:
    return len({state.leaf_of[n] for n in nodes}) > 1


def job_nodes(tasks: Sequence[dict]) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    for t in tasks:
        out.setdefault(t["job"], [])
        if t["node"] not in out[t["job"]]:
            out[t["job"]].append(t["node"])
    return out


def groups(state: State, tasks: Sequence[dict], link: str
           ) -> Dict[str, List[dict]]:
    """Job -> its tasks that source traffic onto ``link``: on a NIC the
    job's pods there; on a leaf's uplink, a job spanning leaves with pods
    in that leaf, its pods there.  LowComm pods are left out.  Departure
    from the paper's Eq. 17: a job's pods on one node count on its NIC even
    when the job runs on that node alone."""
    out: Dict[str, List[dict]] = {}
    if link in state.index:
        for t in tasks:
            if t["node"] == link and not low_comm(t):
                out.setdefault(t["job"], []).append(t)
        return out
    leaf = link[len("uplink:"):]
    for job, nodes in job_nodes(tasks).items():
        if not spans(state, nodes):
            continue
        mine = [t for t in tasks if t["job"] == job and not low_comm(t)
                and state.leaf_of[t["node"]] == leaf]
        if mine:
            out[job] = mine
    return out


def demands(state: State, tasks, link) -> Dict[str, float]:
    return {j: sum(t["bw"] for t in ts)
            for j, ts in groups(state, tasks, link).items()}


def contended(state: State, tasks, link) -> bool:
    """Two jobs or more whose demands together exceed the link's
    allocatable bandwidth: the link needs a rotation."""
    d = demands(state, tasks, link)
    return len(d) > 1 and sum(d.values()) > state.link_alloc[link]


def traversed_leaves(state: State, tasks, job: str) -> List[str]:
    """Leaves whose uplink ``job`` crosses (sorted)."""
    if not state.uplinks:
        return []
    nodes = job_nodes(tasks).get(job, [])
    if not spans(state, nodes):
        return []
    return sorted({state.leaf_of[n] for n in nodes} & set(state.uplinks))


def component(adj: Dict[str, set], start: str) -> set:
    """The vertices reachable from ``start`` in the graph ``adj``."""
    comp, todo = {start}, [start]
    while todo:
        for b in adj.get(todo.pop(), ()):
            if b not in comp:
                comp.add(b)
                todo.append(b)
    return comp


# --------------------------------------------------------- the TDM circle
class Circle(NamedTuple):
    """A unified circle: its base T_l (ms) and, per job, its repetitions
    on it, its implied period T_l / mul, its injected idle, and whether it
    fits the circle (merged or injected) at all."""
    base: float
    muls: List[int]
    eff: List[float]
    inject: List[float]
    ok: List[bool]


def unify(periods: Sequence[float], prios: Sequence[int], g_t: float,
          e_t: float, slots: int) -> Circle:
    """Sec. III-B: one circle for a link's jobs.  The base T_l is a
    multiple of the reference's period (index 0: Eq. 16's highest priority,
    earliest), which is never altered; each job repeats ``round(T_l /
    period)`` times, its implied period T_l / mul.  A job whose implied
    period lies within G_T of its own is merged (averaged onto the circle,
    nothing injected).  A low-priority job other than the reference whose
    implied period is longer by more than G_T and by at most E_T of its own
    period gets the difference injected as idle into its compute phase; a
    high-priority job is never slowed.  Every other job is incompatible: it
    is flagged, not put on the circle.

    Left open by the paper, which asks for the smallest base and warns that
    an excessive one complicates the calculation: the base is at most
    MAX_BASE_MUL reference periods, and no job repeats more than ``slots``
    times (each of its periods then spans a slot of the Di-Pre circle, Eq.
    2); the first base that fits every job wins, else the first that flags
    fewest."""
    n = len(periods)
    low = [i > 0 and prios[i] < HIGH for i in range(n)]
    best: Optional[Circle] = None
    for m in range(1, MAX_BASE_MUL + 1):
        base = periods[0] * m
        muls = [max(1, round(base / p)) for p in periods]
        if max(muls) > slots:
            break
        eff = [base / k for k in muls]
        inject, ok = [0.0] * n, [True] * n
        for i in range(n):
            d = eff[i] - periods[i]
            if abs(d) <= g_t:
                continue
            if low[i] and g_t < d <= e_t * periods[i]:
                inject[i] = d
            else:
                ok[i] = False
        if best is None or sum(ok) > sum(best.ok):
            best = Circle(float(base), muls, eff, inject, ok)
        if all(ok):
            break
    return best


def pattern(mul: int, duty: float, slots: int, start: float = 0.0
            ) -> np.ndarray:
    """Eq. 2 on the discretized circle: ``mul`` arcs of ``duty * slots /
    mul`` slots, evenly spaced from slot ``start``; a slot partly covered
    counts the covered share."""
    pat = np.zeros(slots)
    if duty <= 0:
        return pat
    arc = duty * slots / mul
    for i in range(mul):
        a = start + i * slots / mul
        b = a + arc
        for s in range(math.floor(a), math.ceil(b)):
            cover = min(b, s + 1) - max(a, s)
            if cover > 0:
                pat[s % slots] += cover
    return np.minimum(pat, 1.0)


def eq18(total: np.ndarray, cap: float) -> np.ndarray:
    """Eq. 18 over the last axis: 100 (1 - sum relu(S - C) / (C Di-Pre)).
    ``total`` is overwritten."""
    if cap <= 0:
        return np.zeros(total.shape[:-1])
    slots = total.shape[-1]
    total -= cap
    np.maximum(total, 0.0, out=total)
    ex = total.sum(axis=-1)
    return np.maximum(0.0, 100.0 * (1.0 - ex / (cap * slots)))


def problem(state: State, tasks, links: Sequence[str]) -> Tuple[
        List[str], tuple, tuple, tuple]:
    """One rotation problem over ``links``: every job on them in Eq. 16
    order, each job's (period, duty, priority), each link's demand per job
    (0 where the job is not on it), and the links' capacities."""
    per_link = [demands(state, tasks, l) for l in links]
    seen: Dict[str, None] = {}
    for d in per_link:
        seen.update((j, None) for j in d)
    jobs = state.order(seen)
    spec = {}
    for t in tasks:
        spec.setdefault(t["job"], t)
    specs = tuple((spec[j]["period"], spec[j]["duty"], state.prio.get(j, 0))
                  for j in jobs)
    bw = tuple(tuple(d.get(j, 0.0) for j in jobs) for d in per_link)
    caps = tuple(state.link_alloc[l] for l in links)
    return jobs, specs, bw, caps


@functools.lru_cache(maxsize=256)
def circle_of(specs: tuple, slots: int, g_t: float, e_t: float
              ) -> Tuple[Circle, tuple, tuple]:
    """The problem's circle, each job's pattern at shift 0 (its traffic,
    period x duty, over its implied period) and its Eq. 15 range (a job
    repeating mul times needs only slots // mul shifts; Eq. 16 pins the
    reference)."""
    circ = unify([p for p, _, _ in specs], [q for _, _, q in specs], g_t,
                 e_t, slots)
    pats = tuple(pattern(m, min(1.0, p * d / e), slots)
                 for (p, d, _), m, e in zip(specs, circ.muls, circ.eff))
    ranges = (1,) + tuple(max(1, slots // m) for m in circ.muls[1:])
    return circ, pats, ranges


@functools.lru_cache(maxsize=4096)
def optimum(specs: tuple, bw: tuple, caps: tuple, slots: int, g_t: float,
            e_t: float) -> Tuple[float, Tuple[int, ...]]:
    """(score, shifts) of one rotation problem: the worst link's Eq. 18
    score of every combination of shifts in lexicographic order (the last
    job the fastest digit).  The score is the optimum; the shifts are those
    the Score phase takes: the middle of the first run of perfect
    combinations, else the first best one.  Departure from the paper, which
    gives an incompatible job no place and no score: a problem with one
    scores 0."""
    circ, pats, ranges = circle_of(specs, slots, g_t, e_t)
    if not all(circ.ok):
        return 0.0, (0,) * len(specs)
    n = math.prod(ranges)
    if n > MAX_COMBOS:
        raise TooLarge(f"{n} combinations")
    p = len(specs)
    # each link's Eq. 18 score over the shifts of the jobs on it, laid into
    # the grid of every job's shifts (a job with no demand on a link adds
    # nothing to it); the worst link's score of each combination
    grid = np.full(ranges, np.inf)
    for li, cap in enumerate(caps):
        on = [i for i in range(p) if bw[li][i] != 0]
        if not on:
            continue
        sub = link_grid([pats[i] for i in on], [bw[li][i] for i in on],
                        [ranges[i] for i in on], cap)
        np.minimum(grid, sub.reshape([ranges[i] if i in on else 1
                                      for i in range(p)]), out=grid)
    sc = grid.reshape(-1)
    perfect = np.flatnonzero(sc >= PERFECT - EPS)
    if perfect.size:
        start = int(perfect[0])
        gaps = np.flatnonzero(np.diff(perfect) != 1)
        end = int(perfect[gaps[0]]) if gaps.size else int(perfect[-1])
        return PERFECT, _digits((start + end) // 2, ranges)
    k = int(np.argmax(sc))
    return float(sc[k]), _digits(k, ranges)


def link_grid(pats: Sequence[np.ndarray], bw: Sequence[float],
              ranges: Sequence[int], cap: float) -> np.ndarray:
    """Eq. 18 of one link for every combination of its jobs' shifts:
    an array of shape ``ranges``."""
    p = len(pats)
    slots = len(pats[0])
    if math.prod(ranges) > MAX_COMBOS:
        raise TooLarge(f"{math.prod(ranges)} combinations on one link")
    banks = []
    for i in range(p):
        bank = np.stack([np.roll(pats[i], r) for r in range(ranges[i])])
        shape = [1] * p + [slots]
        shape[i] = ranges[i]
        banks.append(bank.reshape(shape))
    out = np.empty(ranges)
    # blocks: every axis before ``major`` one value at a time, ``major`` in
    # slices, the axes after it whole
    major = 0
    while math.prod(ranges[major + 1:]) > CHUNK:
        major += 1
    step = max(1, CHUNK // math.prod(ranges[major + 1:]))
    for pre in itertools.product(*(range(r) for r in ranges[:major])):
        for a in range(0, ranges[major], step):
            cut = pre + (slice(a, min(ranges[major], a + step)),)
            parts = []
            for i in range(p):
                b = banks[i]
                if i <= major:
                    b = b[(slice(None),) * i + (cut[i],)]
                    if i < major:
                        b = np.expand_dims(b, i)
                parts.append(b)
            total = np.empty(np.broadcast_shapes(*(b.shape for b in parts)))
            total[...] = bw[0] * parts[0]
            for i in range(1, p):
                total += bw[i] * parts[i]
            out[cut] = eq18(total, cap).reshape(out[cut].shape)
    return out


def _digits(k: int, ranges: Sequence[int]) -> Tuple[int, ...]:
    out = []
    for r in reversed(ranges):
        out.append(k % r)
        k //= r
    return tuple(reversed(out))


def solve(state, tasks, links, params, where) -> Tuple[float, dict]:
    """The optimum of the problem over ``links`` and its scheme."""
    jobs, specs, bw, caps = problem(state, tasks, links)
    key = (params["di_pre"], params["g_t_ms"], params["e_t_frac"])
    try:
        score, shifts = optimum(specs, bw, caps, *key)
    except TooLarge as e:
        raise TooLarge(f"{where}: {e} over jobs {jobs} on {list(links)}")
    circ = circle_of(specs, *key)[0]
    return score, {"jobs": jobs, "shifts": shifts, "base": circ.base,
                   "score": score}


# ------------------------------------------------------------ the planner
def link_problem(state, tasks, link, params, where):
    """(score, scheme) of one link; scheme None where nothing contends
    there: no job, only the pod's own, or the demands fit the link."""
    if not contended(state, tasks, link):
        return PERFECT, None
    return solve(state, tasks, [link], params, where)


def plan(state, tasks, links, params, where) -> Tuple[float, dict]:
    """The worst link's score over ``links`` and each contended link's
    scheme.  Links whose chosen shifts give every job pair the same
    relative shift in ms keep their own optimum; a component of jobs whose
    links disagree is solved jointly: one shift per job, the worst of its
    links scored (the system's fabric-wide planner)."""
    schemes, worst = {}, PERFECT
    for l in links:
        score, sch = link_problem(state, tasks, l, params, where)
        worst = min(worst, score)
        if sch is not None:
            schemes[l] = sch
    if len(schemes) < 2:
        return worst, schemes
    slots = params["di_pre"]
    rels: Dict[frozenset, List[float]] = {}
    sign: Dict[frozenset, str] = {}
    adj: Dict[str, set] = {}
    for sch in schemes.values():
        delay = [s / slots * sch["base"] for s in sch["shifts"]]
        for j in sch["jobs"]:
            adj.setdefault(j, set())
        for i, k in itertools.combinations(range(len(sch["jobs"])), 2):
            a, b = sch["jobs"][i], sch["jobs"][k]
            key = frozenset((a, b))
            rel = delay[k] - delay[i]
            sign.setdefault(key, a)
            rels.setdefault(key, []).append(rel if sign[key] == a else -rel)
            adj[a].add(b)
            adj[b].add(a)
    worst, done = PERFECT, set()
    for j0 in adj:
        if j0 in done:
            continue
        comp = component(adj, j0)
        done |= comp
        comp_links = [l for l in schemes if set(schemes[l]["jobs"]) & comp]
        if any(max(v) - min(v) > REL_TOL_MS
               for key, v in rels.items() if key <= comp):
            score, _ = solve(state, tasks, comp_links, params, where)
        else:
            score = min(schemes[l]["score"] for l in comp_links)
        worst = min(worst, score)
    return worst, schemes


def loop_closure(state, tasks, job) -> Tuple[bool, List[str]]:
    """Cassini's affinity loop: job pairs contending on a link (combined
    demand over its allocatable bandwidth) are edges; a cycle of three or
    more jobs through ``job`` whose edges share no link cannot be given
    consistent offsets link by link.  Returns whether one exists, and every
    link of ``job``'s component."""
    edges: Dict[frozenset, set] = {}
    for l in state.links:
        d = demands(state, tasks, l)
        for a, b in itertools.combinations(d, 2):
            if d[a] + d[b] > state.link_alloc[l]:
                edges.setdefault(frozenset((a, b)), set()).add(l)
    adj: Dict[str, set] = {}
    for key in edges:
        a, b = tuple(key)
        adj.setdefault(a, set()).add(b)
        adj.setdefault(b, set()).add(a)
    if job not in adj:
        return False, []
    comp = component(adj, job)
    closure = set()
    for key, ls in edges.items():
        if key <= comp:
            closure |= ls
    return _cycle(adj, edges, job), [l for l in state.links if l in closure]


def _cycle(adj, edges, job) -> bool:
    """A simple cycle of three or more jobs through ``job`` whose edges
    have no link in common."""
    def walk(path, common):
        for b in adj[path[-1]]:
            ls = edges[frozenset((path[-1], b))]
            both = ls if common is None else common & ls
            if b == job and len(path) >= 3:
                if not both:
                    return True
            elif b not in path:
                if walk(path + [b], both):
                    return True
        return False
    return walk([job], None)


def score(state, pod, node, params, where) -> float:
    """The Score extension point for ``pod`` on ``node``."""
    if low_comm(pod):
        return PERFECT
    tasks = state.tasks + [pod_task(pod, node)]
    leaves = traversed_leaves(state, tasks, pod["job"])
    rack = RACK_PENALTY if leaves else 0.0
    links = [node] + [state.uplinks[leaf] for leaf in leaves]
    worst, schemes = plan(state, tasks, links, params, where)
    if not schemes:
        return PERFECT - rack
    loop, closure = loop_closure(state, tasks, pod["job"])
    if loop:
        # departure from the paper, whose Filter drops a candidate that
        # closes a loop: the loop's links are planned jointly instead
        wanted = set(closure) | set(links)
        worst, _ = plan(state, tasks, [l for l in state.links if l in wanted],
                        params, where)
    return max(0.0, worst - rack)


def passes_filter(state, pod, node) -> bool:
    """Spread cap, Eq. 13 and Eq. 14 (NIC, and the uplink where the
    placement makes the job span leaves)."""
    if pod["spread"] > 0:
        same = sum(1 for t in state.tasks
                   if t["job"] == pod["job"] and t["node"] == node)
        if same >= pod["spread"]:
            return False
    if not all(r <= f for r, f in zip(pod["req"], state.free[node])):
        return False
    if pod["bw"] > state.rec["alloc_bw"][node]:
        return False
    if state.uplinks and not low_comm(pod):
        tasks = state.tasks + [pod_task(pod, node)]
        leaf = state.leaf_of[node]
        if leaf in traversed_leaves(state, tasks, pod["job"]):
            if pod["bw"] > state.rec["uplink_alloc"][leaf]:
                return False
    return True


def latency_score(state, pod) -> Dict[str, float]:
    """Delta_n: the latency from each node to the pod's placed
    dependencies (its job's other pods and the pods of jobs its AppGroup
    names); with none, the node's mean latency to every node."""
    partners = {pod["job"]}
    for a, b in state.rec["dependencies"]:
        if a == pod["job"]:
            partners.add(b)
        elif b == pod["job"]:
            partners.add(a)
    lat = state.rec["latency"]
    deps = [t["node"] for t in state.tasks if t["job"] in partners]
    out = {}
    for n in state.nodes:
        i = state.index[n]
        total = sum(lat[i][state.index[m]] for m in deps)
        if total == 0.0:
            total = float(np.mean(lat[i]))
        out[n] = total
    return out


def choose(state, pod, params, where) -> Optional[str]:
    """Filter, Score and NormalizeScore for one pod: the node, None where
    no node passes Filter."""
    feasible = [n for n in state.nodes if passes_filter(state, pod, n)]
    if not feasible:
        return None
    scores = {n: score(state, pod, n, params, f"{where}, node {n}")
              for n in feasible}
    top = max(scores.values())
    best = [n for n in feasible if scores[n] >= top - EPS]
    if len(best) > 1:
        # Eq. 19: the bandwidth-optimal candidates by reverse-mapped
        # latency, the lowest delta 100; LowComm pods take the worst
        delta = latency_score(state, pod)
        lo = min(delta[n] for n in best)
        hi = max(delta[n] for n in best)
        norm = {}
        for n in best:
            v = (100.0 - math.floor(100.0 * (delta[n] - lo) / (hi - lo))
                 if hi != lo else 100.0 - (delta[n] - lo))
            norm[n] = 100.0 - v if low_comm(pod) else v
        scores = {n: norm.get(n, 0.0) for n in feasible}
    return max(feasible, key=lambda n: (scores[n], -state.index[n]))


# -------------------------------------------------------------- the judge
def admission(rec: dict, params: dict) -> Tuple[bool, List[str]]:
    """Each pod of the attempt scheduled after the program's placement of
    the pods before it (the reference's own once the program's are gone).
    Returns whether the reference admits, and its node per pod."""
    state = State(rec)
    got = rec["placed"]
    nodes = []
    for i, pod in enumerate(rec["pods"]):
        where = f"attempt of {rec['job']} at {rec['t_ms']} ms, pod {i}"
        node = choose(state, pod, params, where)
        if node is None:
            return False, nodes
        nodes.append(node)
        state.place(pod, got[i] if got[i] is not None else node)
    return True, nodes


def control_faults(rec: dict, params: dict) -> List[str]:
    """Where the controller's answers after the attempt depart from the
    circles of the live jobs (the record's tasks, with the attempt's pods
    where the program put them if it admitted).

    Every contended link (two jobs or more over its allocatable bandwidth)
    needs a rotation.  Departure from the paper, which unifies per link
    and leaves open a job on two contended links: a job runs one period, so
    contended links that share a job share one circle, unified over all
    their jobs (:func:`unify`).  Each live job then has one period: its
    implied period on its circle, or its own where it is on none or is
    incompatible with it (Sec. III-B).  The controller must answer that
    period (within PERIOD_TOL_MS), and an alignment for every job that fits
    a circle.  With the offsets answered, every contended host link's Eq.
    18 score may not fall below the optimum of its component's rotation
    problem (one shift per job, the worst link scored) by more than EPS."""
    state = State(rec)
    if rec["admitted"]:
        for pod, node in zip(rec["pods"], rec["placed"]):
            state.place(pod, node)
    align = rec["control_after"]["align"]
    key = (params["di_pre"], params["g_t_ms"], params["e_t_frac"])
    tasks = state.tasks
    busy = [l for l in state.links if contended(state, tasks, l)]
    on = {l: set(demands(state, tasks, l)) for l in busy}
    adj = {l: {k for k in busy if on[l] & on[k]} for l in busy}
    want = {}  # job -> (period, its circle's or its own)
    for t in tasks:
        want.setdefault(t["job"], (t["period"], "on no contended link"))
    comps, done = [], set()
    for l0 in busy:
        if l0 in done:
            continue
        links = [l for l in busy if l in component(adj, l0)]
        done |= set(links)
        jobs, specs, bw, caps = problem(state, tasks, links)
        circ = circle_of(specs, *key)[0]
        for i, j in enumerate(jobs):
            want[j] = ((circ.eff[i], "on its circle") if circ.ok[i]
                       else (specs[i][0], "incompatible with its circle"))
        comps.append((links, jobs, specs, bw, caps, circ))
    out, off = [], set()
    for j, (period, how) in want.items():
        got = align.get(j)
        if got is None:
            if how == "on its circle":
                off.add(j)
                out.append(f"{j} has no alignment on its circle")
        elif abs(got[1] - period) > PERIOD_TOL_MS:
            off.add(j)
            out.append(f"{j} answered period {got[1]} ms, {period} ms "
                       f"{how}")
    where = f"attempt of {rec['job']} at {rec['t_ms']} ms, controller"
    for links, jobs, specs, bw, caps, circ in comps:
        if not all(circ.ok) or off & set(jobs):
            continue
        at = [answered_score(row, cap, jobs, specs, circ, align, key[0])
              for row, cap in zip(bw, caps)]
        try:
            # the optimum is at most every link's best over its own jobs'
            # shifts: where no host link falls below that, none falls
            # below the optimum
            bound = min(link_best(specs, row, cap, *key)
                        for row, cap in zip(bw, caps))
            low = [i for i, l in enumerate(links)
                   if l in state.index and at[i] < bound - EPS]
            best = optimum(specs, bw, caps, *key)[0] if low else bound
        except TooLarge as e:
            raise TooLarge(f"{where}: {e} over jobs {jobs} on {links}")
        out.extend(f"{links[i]} scores {at[i]} at the answered offsets, its "
                   f"component's optimum {best}"
                   for i in low if at[i] < best - EPS)
    return out


@functools.lru_cache(maxsize=4096)
def link_best(specs: tuple, bw: tuple, cap: float, slots: int, g_t: float,
              e_t: float) -> float:
    """One link's best Eq. 18 score over the shifts of its own jobs, each
    in its range of the component's problem ``specs`` (``bw`` the link's
    demand per job)."""
    _, pats, ranges = circle_of(specs, slots, g_t, e_t)
    on = [i for i, b in enumerate(bw) if b]
    return float(link_grid([pats[i] for i in on], [bw[i] for i in on],
                           [ranges[i] for i in on], cap).max())


def answered_score(bw, cap, jobs, specs, circ, align, slots) -> float:
    """Eq. 18 of one link with each job's arcs where its answered offset
    puts them on the circle."""
    total = np.zeros(slots)
    for i, j in enumerate(jobs):
        if bw[i]:
            period, duty, _ = specs[i]
            start = align[j][0] % circ.eff[i] / circ.base * slots
            total += bw[i] * pattern(circ.muls[i],
                                     min(1.0, period * duty / circ.eff[i]),
                                     slots, start)
    return float(eq18(total, cap))


def mismatch(rec: dict) -> int:
    """1 when the program's admission departs from the reference's, or its
    controller's answers after it do (:func:`control_faults`), or the
    plugin's constants are not the paper's."""
    params = rec.get("score_params")
    if params != PARAMS:
        return 1
    admitted, nodes = admission(rec, params)
    if admitted != rec["admitted"]:
        return 1
    if any(got is not None and got != node
           for got, node in zip(rec["placed"], nodes)):
        return 1
    if rec["control_after"] is None:
        return 0
    return int(bool(control_faults(rec, params)))
