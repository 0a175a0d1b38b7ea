#!/usr/bin/env python
"""Validate a BENCH_*.json artifact against its result schema.

Usage:  PYTHONPATH=src python scripts/validate_bench.py BENCH_sweep.json
        PYTHONPATH=src python scripts/validate_bench.py BENCH_sched_time.json

Five payload kinds are recognized: experiment sweeps (``sweeps`` key,
the ``--sweep-out`` artifact), benchmark timing rows (``kind == "timing"``,
the ``--bench-out`` artifact), fluid-engine trace-throughput rows
(``kind == "trace_throughput"``, the ``--trace-out`` artifact),
event-loop dynamic-throughput rows (``kind == "dynamic_throughput"``,
the ``--dynamic-out`` artifact), and graceful-degradation rows
(``kind == "robustness"``, the ``--robustness-out`` artifact).  Exit 0
when the file matches
``repro.core.results.SCHEMA_VERSION``'s schema; exit 1 (listing every
problem) on drift — CI runs this after the benchmark smoke so a
silently-changed result format fails the build.
"""
from __future__ import annotations

import json
import sys


def main(argv) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[1]
    from repro.core.results import (validate_bench_dict,
                                    validate_dynamic_throughput_dict,
                                    validate_robustness_dict,
                                    validate_timing_dict,
                                    validate_trace_throughput_dict)

    with open(path) as f:
        doc = json.load(f)
    kind = doc.get("kind") if isinstance(doc, dict) else None
    if kind == "timing":
        problems = validate_timing_dict(doc)
    elif kind == "trace_throughput":
        problems = validate_trace_throughput_dict(doc)
    elif kind == "dynamic_throughput":
        problems = validate_dynamic_throughput_dict(doc)
    elif kind == "robustness":
        problems = validate_robustness_dict(doc)
    else:
        problems = validate_bench_dict(doc)
    if problems:
        print(f"{path}: INVALID ({len(problems)} problems)", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    if kind == "timing":
        rows = doc.get("rows", [])
        origins = sorted({r.get("origin", "") for r in rows})
        print(f"{path}: OK — schema v{doc['schema_version']}, timing, "
              f"{len(rows)} rows from {origins}")
        return 0
    if kind == "trace_throughput":
        rows = doc.get("rows", [])
        best = max((r.get("speedup_vs_python") or 0.0 for r in rows),
                   default=0.0)
        print(f"{path}: OK — schema v{doc['schema_version']}, "
              f"trace_throughput, {len(rows)} rows, best speedup "
              f"{best:.1f}x")
        return 0
    if kind == "dynamic_throughput":
        rows = doc.get("rows", [])
        worst = max((r.get("max_abs_err_vs_oracle") or 0.0 for r in rows),
                    default=0.0)
        print(f"{path}: OK — schema v{doc['schema_version']}, "
              f"dynamic_throughput, {len(rows)} rows, worst oracle error "
              f"{worst:.3g}")
        return 0
    if kind == "robustness":
        rows = doc.get("rows", [])
        worst = max((r.get("degradation") or 0.0 for r in rows),
                    default=0.0)
        axes = sorted({r.get("axis", "") for r in rows})
        print(f"{path}: OK — schema v{doc['schema_version']}, robustness, "
              f"{len(rows)} rows over axes {axes}, worst degradation "
              f"{worst:.2f}x")
        return 0
    n_sweeps = len(doc.get("sweeps", []))
    n_cells = sum(len(s.get("cells", [])) for s in doc.get("sweeps", []))
    n_err = sum(1 for s in doc.get("sweeps", [])
                for c in s.get("cells", []) if c.get("status") != "ok")
    print(f"{path}: OK — schema v{doc['schema_version']}, {n_sweeps} sweeps, "
          f"{n_cells} cells ({n_err} error cells)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
