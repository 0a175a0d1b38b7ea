"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root; its
configuration and traffic are files under ``bench/``.  The run needs as
many TPU chips as the cell asks for: with fewer, or none, it exits
non-zero and prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
every number compared with its limit.  ``--rehearse`` runs the same steps
on the CPU with the ``'jnp'`` fill and reports no metric under a metric's
name.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: 'jnp' fill, no metric reported")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.spec import load_cell

    cell = load_cell(args.workload, ROOT / "BENCHMARK.json")
    out = harness.run(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), root=ROOT, t_start=T_START,
                      rehearse=args.rehearse)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
