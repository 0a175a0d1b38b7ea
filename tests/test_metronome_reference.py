"""Metronome's own policy judged by the plain admission reference of the
benchmark (``bench/admission/metronome.py``: Filter, Eq. 18 Score, Eq. 19
and the controller's answers, after Sec. III-B and Algorithm 1) on a
4-host leaf-spine under seeded streams of about 20 simulated seconds.
Every attempt the harness records (``bench/probes.py``), refusals and
pending-queue retries included, must be the reference's."""
import copy
import json
from pathlib import Path

import pytest

from bench import harness
from bench.probes import Probes
from bench.spec import Cell, load_part

ROOT = Path(__file__).resolve().parents[1]

FABRIC = {
    "nodes": [{"name": f"leaf{l}-host{h}", "cpu": 32, "mem": 256, "gpu": 4,
               "bw_gbps": 25.0} for l in range(2) for h in range(2)],
    "leaves": {f"leaf{l}": [f"leaf{l}-host{h}" for h in range(2)]
               for l in range(2)},
    "oversubscription": 2.0,
}

# the Fig. 10 trace's generator on 16 GPUs, compressed fifty-fold: the
# stream cut 600 s in arrives over ~18 simulated seconds; each seed
# permutes the jobs within blocks of five arrivals
MIX = {"population_seed": 7, "shuffle_block": 5, "time_scale": 0.02,
       "trace": {"generator": "gavel", "duration_s": 7200.0,
                 "total_gpus": 16, "target_load": 0.85,
                 "job_duration_range_s": [120.0, 240.0]},
       "cut_at_s": 600.0, "horizon_s": 900.0}


def _cell():
    cfg = json.loads((ROOT / "bench/configs/testbed-metronome.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg["cluster"] = FABRIC
    return Cell(name="fabric-metronome.test", chips=1,
                config_name="fabric-metronome", config=cfg,
                traffic_name="test", traffic=MIX, end_to_end=[],
                per_layer=[],
                admission=load_part("admission", "metronome", "mismatch"))


@pytest.mark.parametrize("seed", [4100000001, 4100000002, 4100000003,
                                  4100000004, 4100000005])
def test_every_admission_is_the_references(seed):
    cell = _cell()
    assert cell.config["policy"] == {"scheduler": "metronome"}
    sim, _ = harness.build(cell, seed, backend="python")
    probes = Probes(sim, seed=seed, sample_solves=1, trace=False,
                    policy=cell.config["policy"])
    try:
        harness.advance(sim, 20_000.0)
    finally:
        probes.close()
    recs = probes.admissions
    assert sum(r["admitted"] for r in recs) >= 5
    assert any(r["control_after"]["align"] for r in recs)
    bad = [(r["job"], r["t_ms"]) for r in recs if cell.admission(r)]
    assert bad == []
