"""The comparison that decides ``correct`` passes sound runs and fails the
control and every fault, on test-sized cells in CPU rehearsal."""
from __future__ import annotations

import time

import pytest

from bench import control, harness
from bench.spec import load_cell

TRACE = "testbed-k8s.tiny-trace"
PEAK = "tiny-fabric.tiny-peak"
METRONOME = "tiny-metronome.tiny-metro"
# the Metronome cell's stream is run to its end (~23 simulated s): a
# dropped realign shows only where one falls on a realign that moves a job
SECONDS = {METRONOME: 600.0}


def _run(root, cell, seed, kind=None):
    c = load_cell(cell, root / "BENCHMARK.json", root / "bench")
    seconds = SECONDS.get(cell, 1.0)
    t = time.perf_counter()
    if kind is None:
        return harness.run(c, seed=seed, seconds=seconds, trace=False,
                           root=root, t_start=t, rehearse=True,
                           bench_dir=root / "bench")
    with control.installed(kind):
        return harness.run(c, seed=seed, seconds=seconds, trace=False,
                           root=root, t_start=t, rehearse=True,
                           bench_dir=root / "bench")


@pytest.mark.parametrize("cell", [TRACE, PEAK, METRONOME])
@pytest.mark.parametrize("seed", [4000000021, 4000000022])
def test_sound_run_is_correct(tiny_checkout, cell, seed):
    out = _run(tiny_checkout, cell, seed)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell,kind,number", [
    (PEAK, "control", "fill_err_gbps"),
    (TRACE, "control", "progress_gap_ms"),
])
def test_control_is_not_correct(tiny_checkout, cell, kind, number):
    out = _run(tiny_checkout, cell, 4000000023, kind)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", [TRACE, PEAK])
@pytest.mark.parametrize("kind,number", [
    ("frozen", "clock_gap_ms"),
    ("half_batch", "fill_err_gbps"),
    ("altered_rate", "fill_err_gbps"),
    ("altered_placement", "bad_admissions"),
    ("slow_compute", "progress_gap_ms"),
    ("skipped_step", "progress_gap_ms"),
])
def test_fault_is_not_correct(tiny_checkout, cell, kind, number):
    out = _run(tiny_checkout, cell, 4000000024, kind)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("seed", [4000000061, 4000000062, 4000000063,
                                  4000000064, 4000000065])
def test_metronome_admissions_match_the_reference(tiny_checkout, seed):
    """Every attempt of a stream run to its end, judged by the Metronome
    admission reference."""
    out = _run(tiny_checkout, METRONOME, seed)
    assert out["checks"]["bad_admissions"]["value"] == 0


@pytest.mark.parametrize("kind", ["altered_placement", "worse_node",
                                  "false_refusal", "shifted_offset",
                                  "unstretched_period"])
def test_admission_fault_is_not_correct(tiny_checkout, kind):
    """Each fault reads more bad admissions than the sound run of the same
    seed (which the program's own faults keep above 0; PERF.md, section
    7)."""
    sound = _run(tiny_checkout, METRONOME, 4000000024)
    out = _run(tiny_checkout, METRONOME, 4000000024, kind)
    assert not out["correct"]
    assert (out["checks"]["bad_admissions"]["value"]
            > sound["checks"]["bad_admissions"]["value"])


@pytest.mark.parametrize("kind", ["shifted_start", "dropped_pause",
                                  "dropped_inject"])
def test_controller_fault_is_not_correct(tiny_checkout, kind):
    out = _run(tiny_checkout, METRONOME, 4000000024, kind)
    assert not out["correct"]
    c = out["checks"]["progress_gap_ms"]
    assert c["value"] > c["limit"]
