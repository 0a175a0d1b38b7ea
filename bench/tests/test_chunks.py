"""Chunks of simulated time, and the admission timer."""
from __future__ import annotations

import json

import pytest

from bench import harness
from bench.probes import Probes
from bench.spec import load_cell


def _cell(root, name):
    return load_cell(name, root / "BENCHMARK.json", root / "bench")


def _state(sim):
    return ({n: st.job.nodes_used() for n, st in sim.jobs.items()},
            {n: st.iter_index for n, st in sim.jobs.items()},
            {n: list(st.durations_ms) for n, st in sim.jobs.items()},
            sim.pending_jobs)


@pytest.mark.parametrize("cell", ["testbed-k8s.tiny-trace",
                                  "tiny-fabric.tiny-peak"])
def test_same_chunking_agrees_exactly(tiny_checkout, cell):
    c = _cell(tiny_checkout, cell)
    chunk = float(c.traffic["chunk_sim_s"]) * 1e3
    runs = []
    for _ in range(2):
        sim, _ = harness.build(c, 4000000011, backend="python")
        for k in range(1, 9):
            harness.advance(sim, k * chunk)
            assert sim.now == k * chunk
        runs.append(_state(sim))
    assert runs[0] == runs[1]
    assert sum(runs[0][1].values()) > 0


def test_admission_probe_counts_every_attempt(tiny_checkout, monkeypatch):
    """A cluster too small for the 4-pod jobs (spread 1, 2 hosts) keeps
    them queued, so each departure retries them: every call is counted."""
    from repro.core.simulator import ClusterSimulator

    c = _cell(tiny_checkout, "tiny-fabric.tiny-peak")
    c.config = json.loads(json.dumps(c.config))
    c.config["cluster"] = {"nodes": [
        {"name": f"host{h}", "cpu": 32, "mem": 256, "gpu": 4,
         "bw_gbps": 25.0} for h in range(2)]}
    calls = []
    orig = ClusterSimulator._try_schedule

    def counting(self, wl):
        calls.append(wl.name)
        return orig(self, wl)

    monkeypatch.setattr(ClusterSimulator, "_try_schedule", counting)
    sim, _ = harness.build(c, 4000000013, backend="python")
    probes = Probes(sim, seed=1, sample_solves=10, trace=False)
    harness.advance(sim, 500.0)
    probes.on = True
    calls.clear()
    harness.advance(sim, 3_600_000.0)
    probes.on = False
    probes.close()
    assert len(calls) == probes.admit_calls
    assert len(calls) > len(set(calls))          # retries from the queue
    assert any(not r["admitted"] for r in probes.admissions)
