"""Fixtures of the benchmark's own tests (CPU only).

    python -m pytest bench/tests

``tiny_checkout`` builds a checkout in a temporary directory that holds a
copy of ``bench/``, the program's ``src/`` (linked), and a BENCHMARK.json
whose three small cells are made only of new files: two configurations
(one under the full Metronome policy, judged by the Metronome admission
reference), three traffic mixes and a metric that the repository does not
have.  It is how a later change adds a cell, and it
is small enough for a test to run.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

TINY_FABRIC = {
    "nodes": [{"name": f"leaf{l}-host{h}", "cpu": 32, "mem": 256, "gpu": 4,
               "bw_gbps": 25.0} for l in range(2) for h in range(2)],
    "leaves": {f"leaf{l}": [f"leaf{l}-host{h}" for h in range(2)]
               for l in range(2)},
    "oversubscription": 2.0,
}

METRONOME_CELL = "tiny-metronome.tiny-metro"

NEW_METRIC = '''"""Event-loop ticks per wall second over the window."""


def read(win):
    return win.profile["ticks"] / win.wall_s
'''


def tiny_files(root: Path) -> None:
    """Write the tiny cells' configuration, traffic and metric files and
    a BENCHMARK.json that names them beside the real cell: a
    small leaf-spine under a production day's peak and, under the full
    Metronome policy, under a compressed trace followed whole; and the
    testbed under a stream of short jobs."""
    bench = root / "bench"
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/testbed-k8s.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(name="tiny-fabric", cluster=TINY_FABRIC)
    (bench / "configs" / "tiny-fabric.json").write_text(json.dumps(cfg))
    metro = copy.deepcopy(cfg)
    metro.update(name="tiny-metronome", policy={"scheduler": "metronome"})
    metro["check"]["admission"] = "metronome"
    (bench / "configs" / "tiny-metronome.json").write_text(json.dumps(metro))
    for name, why in (("tiny-fabric", "a test-sized leaf-spine"),
                      ("tiny-metronome", "the same under the full policy")):
        spec["configs"].append({
            "name": name, "source": "https://arxiv.org/abs/2510.12274",
            "file": f"bench/configs/{name}.json", "reduced": [],
            "why": why})
    for mix, extra in (
            ("tiny-trace", {"trace": {
                "generator": "gavel", "duration_s": 7200.0,
                "total_gpus": 13, "target_load": 0.85,
                "job_duration_range_s": [60.0, 120.0]},
                "chunk_sim_s": 5.0, "warmup_sim_s": 5.0}),
            ("tiny-peak", {"trace": {"n_jobs": 4000}, "cut_at_s": 50400.0,
                           "horizon_s": 600.0, "chunk_sim_s": 0.5,
                           "warmup_sim_s": 0.5}),
            # chunks no longer than follow_sim_s: every chunk followed
            # whole; the stream ends ~23 simulated s in
            ("tiny-metro", {"trace": {
                "generator": "gavel", "duration_s": 7200.0,
                "total_gpus": 16, "target_load": 0.85,
                "job_duration_range_s": [120.0, 240.0]},
                "cut_at_s": 600.0, "horizon_s": 900.0, "time_scale": 0.02,
                "chunk_sim_s": 1.0, "warmup_sim_s": 1.0})):
        data = {"population_seed": 7, "shuffle_block": 5, "time_scale": 1.0,
                "sample_solves": 500, "fill_max_flows": 32,
                "fill_max_links": 32, **extra}
        (bench / "traffic" / f"{mix}.json").write_text(json.dumps(data))
    spec["workloads"] += [
        {"name": "testbed-k8s.tiny-trace", "config": "testbed-k8s",
         "traffic": "tiny-trace", "chips": 1, "why": "test cell"},
        {"name": "tiny-fabric.tiny-peak", "config": "tiny-fabric",
         "traffic": "tiny-peak", "chips": 1, "why": "test cell"},
        {"name": METRONOME_CELL, "config": "tiny-metronome",
         "traffic": "tiny-metro", "chips": 1, "why": "test cell"},
    ]
    (bench / "metrics" / "loop.ticks_per_s.py").write_text(NEW_METRIC)
    spec["per_layer"].append({
        "name": "loop.ticks_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "event loop",
        "moves": "sim_rate",
        "workloads": ["testbed-k8s.tiny-trace", "tiny-fabric.tiny-peak",
                      METRONOME_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


@pytest.fixture(scope="session")
def tiny_checkout(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    tiny_files(root)
    return root
