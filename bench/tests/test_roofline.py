"""The fill roofline's byte count and share."""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.harness import load_reader
from bench.probes import fill_bytes


def _problem(f, l, demand=10.0):
    return (np.full(f, demand, np.float32), np.ones((f, l), np.float32),
            np.full(l, 25.0, np.float32))


def test_fill_bytes_counts_real_problems_unpadded():
    dummy = (np.zeros(1, np.float32), np.zeros((1, 1), np.float32),
             np.ones(1, np.float32))
    batch = [_problem(3, 2), _problem(1, 1)] + [dummy] * 62
    # (demands + routes + caps + rates) x 4 bytes
    assert fill_bytes(batch) == 4 * (3 + 6 + 2 + 3) + 4 * (1 + 1 + 1 + 1)


def test_roofline_share_is_bytes_over_peak_over_device_time():
    read = load_reader("metronome_fill_roofline")
    trace = SimpleNamespace(program_s={"jit_metronome_fill": 2e-3,
                                       "jit_other": 5.0})
    win = SimpleNamespace(trace=trace, fill_bytes=819_000,
                          peaks={"hbm_bytes_per_s": 819e9})
    # 819 kB at 819 GB/s is 1 us of the 2 ms the fill's program ran
    assert abs(read(win) - 0.05) < 1e-12


def test_roofline_silent_without_fill():
    read = load_reader("metronome_fill_roofline")
    trace = SimpleNamespace(program_s={"jit_other": 1.0})
    win = SimpleNamespace(trace=trace, fill_bytes=100,
                          peaks={"hbm_bytes_per_s": 819e9})
    assert read(win) is None
    win.fill_bytes = 0
    assert read(win) is None
