"""The Kubernetes default scheduler's admission, as a plain reference that
imports nothing of the program: NodeResourcesFit plus the spread cap
filter, the LeastAllocated score, ties to the lowest node index, all or
nothing.  A configuration names it as ``"check": {"admission":
"least_allocated"}``; :func:`mismatch` judges one attempt's record (see
``bench/probes.py``)."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

RESOURCES = ("cpu", "mem", "gpu")


def _fits(req: Sequence[float], free: Sequence[float]) -> bool:
    return all(r <= f for r, f in zip(req, free))


def least_allocated(rec: dict) -> Tuple[bool, Optional[List[str]]]:
    """The default scheduler's outcome for one admission attempt ``rec``:
    (admitted, node per pod)."""
    nodes = rec["nodes"]
    free = {n: list(rec["free"][n]) for n in nodes}
    per_node: Dict[str, int] = {}
    placed: List[str] = []
    for pod in rec["pods"]:
        best, best_key = None, None
        for idx, n in enumerate(nodes):
            if pod["spread"] > 0 and per_node.get(n, 0) >= pod["spread"]:
                continue
            if not _fits(pod["req"], free[n]):
                continue
            cap = rec["capacity"][n]
            terms = [(free[n][k] - pod["req"][k]) / cap[k]
                     for k in range(len(RESOURCES)) if cap[k] > 0]
            score = 100.0 * (sum(terms) / len(terms)) if terms else 0.0
            key = (score, -idx)
            if best_key is None or key > best_key:
                best, best_key = n, key
        if best is None:
            return False, None
        free[best] = [f - r for f, r in zip(free[best], pod["req"])]
        per_node[best] = per_node.get(best, 0) + 1
        placed.append(best)
    return True, placed


def mismatch(rec: dict) -> int:
    """1 when the program's outcome differs from :func:`least_allocated`."""
    ok, placed = least_allocated(rec)
    if ok != rec["admitted"]:
        return 1
    return int(ok and placed != rec["placed"])
