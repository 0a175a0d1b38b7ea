#!/usr/bin/env python
"""Cross-commit diff of BENCH_*.json artifacts with regression gates.

Usage:
  PYTHONPATH=src python scripts/diff_bench.py BASELINE CURRENT \
      [--rel-tol 1e-6] [--timing-ratio 25] [--min-speedup 50]

CI uploads the benchmark artifacts on every push but (until now) never
compared them — a silent result regression survived as long as the schema
stayed valid.  This script closes that gap: the tier-1 job diffs the fresh
smoke artifacts against the committed ``benchmarks/baselines/`` copies.

Gates per payload kind (sniffed from the files, which must match):

  * experiment sweeps (``BENCH_sweep.json``): sweeps are seeded and
    deterministic, so every numeric leaf of every cell result must match
    the baseline within ``--rel-tol`` (default 1e-6).  A cell present in
    the baseline but missing from the current run fails; brand-new cells
    (new benches / scenarios) pass with a note.
  * timing rows (``BENCH_sched_time.json``): wall-clock is noisy on shared
    runners, so the gate is loose — a row fails only when it got more than
    ``--timing-ratio`` times slower than baseline (default 25x, i.e. an
    accidental algorithmic blow-up, not jitter).
  * trace throughput (``BENCH_trace_throughput.json``): the vectorized
    backends must keep ``speedup_vs_python >= --min-speedup`` (default 10
    — the committed artifact records ~70x, the acceptance floor is 50x on
    dedicated hardware; CI runners are slower and noisier).
  * dynamic throughput (``BENCH_dynamic_throughput.json``): a row
    present in the baseline must be present now, and every row's
    ``max_abs_err_vs_oracle`` must stay within ``--max-abs-err`` (default
    1e-6).
  * robustness (``BENCH_robustness.json``): runs are seeded and
    deterministic, so every numeric field of every (axis, scenario,
    policy, x) row must match within ``--rel-tol`` — the committed
    baseline pins the whole graceful-degradation curve, including the
    robust policy's shallower failure-axis slope.

Exit 0 = no regression, 1 = regression(s) listed on stderr, 2 = usage.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterator, List, Tuple


def _kind(doc: Any) -> str:
    if isinstance(doc, dict):
        if doc.get("kind") in ("timing", "trace_throughput",
                               "dynamic_throughput", "robustness"):
            return doc["kind"]
        if "sweeps" in doc:
            return "sweeps"
    return "unknown"


def _numeric_leaves(obj: Any, prefix: str = "") -> Iterator[Tuple[str, Any]]:
    """Yield (dotted.path, value) for every scalar leaf; lists are skipped
    except as lengths (per-iteration duration samples are trajectories we
    deliberately do not pin)."""
    if isinstance(obj, dict):
        for k in sorted(obj):
            yield from _numeric_leaves(obj[k], f"{prefix}.{k}" if prefix else k)
    elif isinstance(obj, list):
        yield f"{prefix}.len", len(obj)
    else:
        yield prefix, obj


def _close(a: Any, b: Any, rel_tol: float) -> bool:
    if a is None or b is None:
        return a is None and b is None  # NaN serializes to null
    if isinstance(a, bool) or isinstance(b, bool) or \
            isinstance(a, str) or isinstance(b, str):
        return a == b
    a, b = float(a), float(b)
    return abs(a - b) <= rel_tol * max(1.0, abs(a), abs(b))


def diff_sweeps(base: Dict, cur: Dict, rel_tol: float) -> List[str]:
    def cells(doc: Dict) -> Dict[Tuple[str, str, str], Dict]:
        out = {}
        for sw in doc.get("sweeps", []):
            origin = str(sw.get("meta", {}).get("origin", ""))
            for c in sw.get("cells", []):
                out[(origin, c.get("scenario"), c.get("policy"))] = c
        return out

    b, c = cells(base), cells(cur)
    problems = []
    for key in sorted(set(b) - set(c)):
        problems.append(f"cell {key} present in baseline, missing now")
    for key in sorted(set(c) - set(b)):
        print(f"note: new cell {key} (no baseline)", file=sys.stderr)
    for key in sorted(set(b) & set(c)):
        cb, cc = b[key], c[key]
        if cb.get("status") != cc.get("status"):
            problems.append(f"cell {key}: status {cb.get('status')!r} -> "
                            f"{cc.get('status')!r}")
            continue
        lb = dict(_numeric_leaves(cb.get("result", {})))
        lc = dict(_numeric_leaves(cc.get("result", {})))
        for path in sorted(set(lb) - set(lc)):
            problems.append(f"cell {key}: field {path} disappeared")
        for path in sorted(set(lb) & set(lc)):
            if not _close(lb[path], lc[path], rel_tol):
                problems.append(f"cell {key}: {path} {lb[path]!r} -> "
                                f"{lc[path]!r} (rel tol {rel_tol})")
    return problems


def diff_timings(base: Dict, cur: Dict, ratio: float) -> List[str]:
    def rows(doc: Dict) -> Dict[Tuple[str, str], float]:
        return {(r.get("origin", ""), r["name"]): r.get("us_per_call")
                for r in doc.get("rows", [])}

    b, c = rows(base), rows(cur)
    problems = []
    for key in sorted(set(b) - set(c)):
        problems.append(f"timing row {key} present in baseline, missing now")
    for key in sorted(set(c) - set(b)):
        print(f"note: new timing row {key} (no baseline)", file=sys.stderr)
    for key in sorted(set(b) & set(c)):
        vb, vc = b[key], c[key]
        if not vb or vc is None:
            continue
        if vc > vb * ratio:
            problems.append(f"timing row {key}: {vb:.1f}us -> {vc:.1f}us "
                            f"(> {ratio}x slower)")
    return problems


def diff_trace(base: Dict, cur: Dict, min_speedup: float) -> List[str]:
    problems = []
    names_cur = {r["name"] for r in cur.get("rows", [])}
    for r in base.get("rows", []):
        if r["name"] not in names_cur:
            problems.append(f"trace row {r['name']!r} present in baseline, "
                            f"missing now")
    for r in cur.get("rows", []):
        if r.get("backend") != "python" and \
                (r.get("speedup_vs_python") or 0.0) < min_speedup:
            problems.append(f"trace row {r['name']!r}: speedup "
                            f"{r.get('speedup_vs_python')} < {min_speedup}x")
    return problems


def diff_dynamic(base: Dict, cur: Dict, max_err: float) -> List[str]:
    problems = []
    names_cur = {r["name"] for r in cur.get("rows", [])}
    for r in base.get("rows", []):
        if r["name"] not in names_cur:
            problems.append(f"dynamic row {r['name']!r} present in "
                            f"baseline, missing now")
    for r in cur.get("rows", []):
        if (r.get("max_abs_err_vs_oracle") or 0.0) > max_err:
            problems.append(f"dynamic row {r['name']!r}: max_abs_err "
                            f"{r.get('max_abs_err_vs_oracle')} > {max_err}")
    return problems


def diff_robustness(base: Dict, cur: Dict, rel_tol: float) -> List[str]:
    def rows(doc: Dict) -> Dict[Tuple[str, str, str, Any], Dict]:
        return {(r.get("axis"), r.get("scenario"), r.get("policy"),
                 r.get("x")): r for r in doc.get("rows", [])}

    b, c = rows(base), rows(cur)
    problems = []
    for key in sorted(set(b) - set(c)):
        problems.append(f"robustness row {key} present in baseline, "
                        f"missing now")
    for key in sorted(set(c) - set(b)):
        print(f"note: new robustness row {key} (no baseline)",
              file=sys.stderr)
    for key in sorted(set(b) & set(c)):
        rb, rc = b[key], c[key]
        for field in sorted(set(rb) | set(rc)):
            if field in ("axis", "scenario", "policy", "origin"):
                continue
            if not _close(rb.get(field), rc.get(field), rel_tol):
                problems.append(f"robustness row {key}: {field} "
                                f"{rb.get(field)!r} -> {rc.get(field)!r} "
                                f"(rel tol {rel_tol})")
    return problems


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("baseline")
    ap.add_argument("current")
    ap.add_argument("--rel-tol", type=float, default=1e-6,
                    help="relative tolerance for sweep result fields")
    ap.add_argument("--timing-ratio", type=float, default=25.0,
                    help="fail a timing row slower than baseline by this "
                         "factor")
    ap.add_argument("--min-speedup", type=float, default=10.0,
                    help="minimum speedup_vs_python for vectorized "
                         "trace-throughput rows")
    ap.add_argument("--max-abs-err", type=float, default=1e-6,
                    help="maximum max_abs_err_vs_oracle for "
                         "dynamic-throughput rows")
    args = ap.parse_args(argv[1:])

    with open(args.baseline) as f:
        base = json.load(f)
    with open(args.current) as f:
        cur = json.load(f)
    kb, kc = _kind(base), _kind(cur)
    if kb != kc or kb == "unknown":
        print(f"payload kinds differ or unknown: baseline={kb} current={kc}",
              file=sys.stderr)
        return 2
    if base.get("smoke") != cur.get("smoke"):
        print(f"smoke flags differ: baseline={base.get('smoke')} "
              f"current={cur.get('smoke')} — comparing anyway",
              file=sys.stderr)
    if kb == "sweeps":
        problems = diff_sweeps(base, cur, args.rel_tol)
    elif kb == "timing":
        problems = diff_timings(base, cur, args.timing_ratio)
    elif kb == "dynamic_throughput":
        problems = diff_dynamic(base, cur, args.max_abs_err)
    elif kb == "robustness":
        problems = diff_robustness(base, cur, args.rel_tol)
    else:
        problems = diff_trace(base, cur, args.min_speedup)
    if problems:
        print(f"{args.current}: {len(problems)} regression(s) vs "
              f"{args.baseline}", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print(f"{args.current}: no regressions vs {args.baseline} ({kb})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
