"""Per-tick cost of the event loop's flow passes against the size of the
active set: the scalar pass over the membership cache's lists against
numpy's vector calls.  ``simulator._SCALAR_MAX_FLOWS`` sits at their
crossover (DESIGN.md section 17).

    PYTHONPATH=src python -m benchmarks.loop_crossover [--sizes 4,8,16]

Each size builds a synthetic active set on a star cluster, one flow per
job, every flow on its own host link, or with ``--two-link`` also on the
next host's link, and times next-event plus advance (``_next_flow_finish``
then ``_advance_flows``) over ticks in which no flow finishes and over
ticks in which one does, both paths in alternating rounds.  Prints one
JSON line per size: the median microseconds per tick of each path.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import time

import numpy as np

from repro.core import simulator
from repro.core.cluster import Cluster, Node, Resources
from repro.core.simulator import ClusterSimulator, SimConfig


def _sim(n_flows: int, two_link: bool) -> ClusterSimulator:
    nodes = [Node(f"n{i}", Resources(cpu=32, mem=256, gpu=4), bw_gbps=25.0)
             for i in range(n_flows)]
    sim = ClusterSimulator(Cluster(nodes), [], SimConfig())
    sim._junfin = np.ones(max(64, n_flows), dtype=np.int64)
    tbl = sim._flows
    for j in range(n_flows):
        path = (f"n{j}",)
        if two_link:
            path += (f"n{(j + 1) % n_flows}",)
        (s,) = tbl.reserve(j, [10.0], [path]).tolist()
        tbl.alive[s] = True
        tbl.rate[s] = 10.0
        tbl.remaining[s] = 1e6
        sim._active_bits |= 1 << s
    return sim


def _per_tick_us(sim: ClusterSimulator, cutoff: int, finish: bool,
                 ticks: int) -> float:
    """Mean microseconds of ``ticks`` ticks under ``cutoff``: 1 ms ticks
    in which no flow finishes, or with ``finish`` ticks that end at the
    first flow's finish, the state put back outside the timed part."""
    simulator._SCALAR_MAX_FLOWS = cutoff
    sim._active_cache.clear()
    sim._act_bits = -1
    act = sim._active_slots()
    tbl = sim._flows
    first = int(act[0])
    bits, junfin = sim._active_bits, sim._junfin.copy()
    perf = time.perf_counter
    total = 0.0
    for _ in range(ticks):
        if finish:
            tbl.remaining[first] = 0.5  # ends at 50 ms, the next event
        t0 = perf()
        nxt, gathered = sim._next_flow_finish(act, math.inf)
        sim._advance_flows(act, nxt if finish else 1.0, gathered)
        total += perf() - t0
        if finish:
            tbl.remaining[act] = 1e6
            sim._active_bits = bits
            sim._junfin[:] = junfin
            sim._dirty_links.clear()
    return total / ticks * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes",
                    default="4,8,12,16,20,24,28,32,40,48,64,128,256")
    ap.add_argument("--ticks", type=int, default=2000)
    ap.add_argument("--rounds", type=int, default=7)
    ap.add_argument("--two-link", action="store_true")
    args = ap.parse_args()
    default = simulator._SCALAR_MAX_FLOWS
    try:
        for n in (int(x) for x in args.sizes.split(",")):
            sim = _sim(n, args.two_link)
            row = {"flows": n, "two_link": args.two_link}
            for finish in (False, True):
                got = {"scalar": [], "vector": []}
                for _ in range(args.rounds):
                    for path, cutoff in (("scalar", n), ("vector", 0)):
                        got[path].append(
                            _per_tick_us(sim, cutoff, finish, args.ticks))
                tag = "finish" if finish else "steady"
                for path, vals in got.items():
                    row[f"{path}_{tag}_us"] = statistics.median(vals)
            print(json.dumps(row), flush=True)
    finally:
        simulator._SCALAR_MAX_FLOWS = default


if __name__ == "__main__":
    main()
