"""The simulator's value goldens: ``tests/goldens/sim_goldens.json``.

Every run the tests pin by value is made by the test modules' own run
functions (``GOLDEN_RUNS`` of ``tests/test_event_loop.py`` and
``tests/test_robustness.py``), run, and stored under its name: every
field ``sim_equal`` compares, floats as ``float.hex`` (NaN, inf and every
bit survive the round trip), and, for experiment results, the accepted,
rejected and placed jobs.

A change that alters simulated results on purpose regenerates the file
from the repository root:

    PYTHONPATH=src python tests/goldens/record.py
"""
from __future__ import annotations

import functools
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, Tuple

FIXTURE = Path(__file__).with_name("sim_goldens.json")

# the SimResult fields sim_equal compares
FIELDS = ("durations_ms", "time_per_1000_iters_s", "link_utilization",
          "avg_bw_utilization", "readjustments", "finish_times_ms",
          "total_completion_ms", "iterations_done", "reconfigurations")
# what an experiment result adds beside its SimResult
EXTRAS = ("accepted", "rejected", "placements")


def _encode(value: Any) -> Any:
    if isinstance(value, float):
        return value.hex()
    if isinstance(value, dict):
        return {k: _encode(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_encode(v) for v in value]
    return value


def _decode(value: Any) -> Any:
    if isinstance(value, str):
        return float.fromhex(value)
    if isinstance(value, dict):
        return {k: _decode(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_decode(v) for v in value]
    return value


def result_of(out: Any) -> Tuple[Any, Dict[str, Any]]:
    """The SimResult in what a run function returns — a ``(simulator,
    SimResult)`` pair or an experiment result — and the experiment's
    extras."""
    if isinstance(out, tuple):
        return out[1], {}
    extras = {k: getattr(out, k) for k in EXTRAS if hasattr(out, k)}
    return out.sim, json.loads(json.dumps(extras))


def encode(out: Any) -> Dict[str, Any]:
    res, extras = result_of(out)
    entry = {f: _encode(getattr(res, f)) for f in FIELDS}
    entry.update(extras)
    return entry


@functools.lru_cache(maxsize=None)
def load() -> Dict[str, Dict[str, Any]]:
    with open(FIXTURE) as f:
        return json.load(f)


def golden(name: str):
    """The pinned ``SimResult`` of run ``name`` and its extras."""
    from repro.core.simulator import SimResult

    entry = load()[name]
    res = SimResult(**{f: _decode(entry[f]) for f in FIELDS})
    return res, {k: entry[k] for k in EXTRAS if k in entry}


def runs() -> Dict[str, Callable[..., Any]]:
    """Every pinned run's function, by name, from the test modules."""
    import test_event_loop
    import test_robustness

    out = dict(test_event_loop.GOLDEN_RUNS)
    dup = set(out) & set(test_robustness.GOLDEN_RUNS)
    if dup:
        raise ValueError(f"golden runs named twice: {sorted(dup)}")
    out.update(test_robustness.GOLDEN_RUNS)
    return out


def main() -> int:
    entries = {}
    for name, build in runs().items():
        entries[name] = encode(build())
        print(f"{name}: recorded", file=sys.stderr)
    text = json.dumps(entries, indent=1, sort_keys=True, allow_nan=False)
    FIXTURE.write_text(text + "\n")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    sys.exit(main())
