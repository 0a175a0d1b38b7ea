"""A cell, a configuration, a traffic mix and a per-layer metric are
added by adding files and entries only, and run end to end."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

CELLS = ["testbed-k8s.tiny-trace", "tiny-fabric.tiny-peak",
         "tiny-metronome.tiny-metro"]


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_new_cell_runs_in_rehearsal(tiny_checkout, cell, trace):
    p = _run(tiny_checkout, "--workload", cell, "--seed", "4000000007",
             "--seconds", "1", "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"] == {}          # a CPU run reports no metric
    assert out["device"]["platform"] == "cpu"
    got = out["rehearsal_metrics"]
    if trace == "0":
        assert {"sim_rate", "setup_s"} <= set(got)
    else:
        assert "loop.ticks_per_s" in got  # the metric added as a file
        assert "sim_rate" not in got
    assert list(out)[-1] == "checks"


def test_no_chip_no_result(tiny_checkout):
    p = _run(tiny_checkout, "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_do_not_run(tiny_checkout, tmp_path):
    shutil.copytree(tiny_checkout / "bench", tmp_path / "bench")
    shutil.copy(tiny_checkout / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
