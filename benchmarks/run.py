"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each bench_* module for
the paper artifact it reproduces; the mapping lives in DESIGN.md section 7).
"""
from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path

from repro.compile_cache import enable_compile_cache

from . import (bench_ablation, bench_dynamic, bench_dynamic_throughput,
               bench_fabric, bench_kernels, bench_param_variation,
               bench_persistence, bench_robustness, bench_roofline,
               bench_rotation, bench_sched_time, bench_snapshots, bench_tct,
               bench_thresholds, bench_trace_throughput, common)

ALL = {
    "snapshots": bench_snapshots,     # Fig. 7/8 + Table V
    "fabric": bench_fabric,           # beyond-paper: oversubscribed fabrics
    "dynamic": bench_dynamic,         # beyond-paper: mid-run fluctuation
    "rotation": bench_rotation,       # beyond-paper: joint planner vs legacy
    "tct": bench_tct,                 # Fig. 10
    "param_variation": bench_param_variation,  # Fig. 11/12
    "persistence": bench_persistence,  # Table VI
    "ablation": bench_ablation,       # Tables VII/VIII + Fig. 13
    "thresholds": bench_thresholds,   # Fig. 14/15
    "sched_time": bench_sched_time,   # Fig. 16
    "kernels": bench_kernels,         # kernel micro-benches
    "roofline": bench_roofline,       # dry-run roofline summary
    "trace_throughput": bench_trace_throughput,  # fluid-engine backends @ 10k jobs
    "dynamic_throughput": bench_dynamic_throughput,  # event loops @ 10k-job trace
    "robustness": bench_robustness,   # imperfect telemetry + fault injection
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny iteration counts / durations: every bench "
                         "runs end-to-end fast (CI keeps the scripts alive)")
    ap.add_argument("--sweep-out", default=None, metavar="PATH",
                    help="write every experiment sweep the benches ran as "
                         "schema-versioned JSON (CI: BENCH_sweep.json, "
                         "validated by scripts/validate_bench.py)")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="write every emit() timing row as schema-versioned "
                         "JSON (CI: BENCH_sched_time.json, validated by "
                         "scripts/validate_bench.py)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the fluid-engine trace-throughput rows as "
                         "schema-versioned JSON (CI nightly: "
                         "BENCH_trace_throughput.json)")
    ap.add_argument("--dynamic-out", default=None, metavar="PATH",
                    help="write the event-loop dynamic-throughput rows as "
                         "schema-versioned JSON (CI nightly: "
                         "BENCH_dynamic_throughput.json)")
    ap.add_argument("--robustness-out", default=None, metavar="PATH",
                    help="write the graceful-degradation rows as "
                         "schema-versioned JSON (CI: BENCH_robustness.json, "
                         "validated by scripts/validate_bench.py)")
    ap.add_argument("--workers", type=int, default=1, metavar="N",
                    help="fan independent sweep cells over N workers "
                         "(results identical to serial; default 1)")
    ap.add_argument("--worker-mode", default="thread",
                    choices=("thread", "process"),
                    help="worker pool flavor for --workers > 1: threads "
                         "(default) or spawned processes (sidesteps the "
                         "GIL for CPU-bound grids; scenario builders are "
                         "picklable dataclasses so cells ship cleanly). "
                         "Process mode refuses grids on a device fluid "
                         "backend ('jnp'/'kernel'): one process per chip")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="content-keyed sweep-result cache (nightly CI): "
                         "grids whose materialized inputs are unchanged "
                         "restore from DIR instead of re-simulating")
    args = ap.parse_args()
    enable_compile_cache(Path(__file__).resolve().parents[1])
    if args.smoke:
        common.SMOKE = True
    common.WORKERS = max(1, args.workers)
    common.WORKER_MODE = args.worker_mode
    common.CACHE_DIR = args.cache_dir
    names = args.only.split(",") if args.only else list(ALL)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        common.CURRENT_ORIGIN = name
        try:
            ALL[name].run()
        except Exception:  # noqa: BLE001 — keep the harness going
            traceback.print_exc()
            failed.append(name)
        finally:
            common.CURRENT_ORIGIN = ""
    if args.sweep_out:
        common.write_sweeps(args.sweep_out)
        print(f"# wrote {len(common.RECORDED_SWEEPS)} sweeps to "
              f"{args.sweep_out}", file=sys.stderr)
    if args.bench_out:
        common.write_timings(args.bench_out)
        print(f"# wrote {len(common.RECORDED_EMITS)} timing rows to "
              f"{args.bench_out}", file=sys.stderr)
    if args.trace_out:
        common.write_trace_throughput(args.trace_out)
        print(f"# wrote {len(common.RECORDED_TRACE_ROWS)} trace-throughput "
              f"rows to {args.trace_out}", file=sys.stderr)
    if args.dynamic_out:
        common.write_dynamic_throughput(args.dynamic_out)
        print(f"# wrote {len(common.RECORDED_DYNAMIC_ROWS)} "
              f"dynamic-throughput rows to {args.dynamic_out}",
              file=sys.stderr)
    if args.robustness_out:
        common.write_robustness(args.robustness_out)
        print(f"# wrote {len(common.RECORDED_ROBUSTNESS_ROWS)} "
              f"robustness rows to {args.robustness_out}",
              file=sys.stderr)
    if failed:
        print(f"# FAILED benches: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
