"""Name an idle gap of the device by the program's own host spans.

The program opens ``jax.profiler.TraceAnnotation`` spans inside its event
loop (``sim.assign``, ``sim.next_event``, ``sim.advance``, ``sim.events``,
``sim.step``, ``sim.admit``) and its rate solve (``fluid.components``,
``fluid.problems``, ``fluid.solve_batch``, ``fluid.key``, ``fluid.pack``,
``fluid.device``) while a profiler session records.  A gap of a second
holds thousands of such tick-sized spans, so no single one covers much of
it, and :mod:`bench.devtrace`, which names a gap by the one harness span
(``bench.*``) covering most of it, cannot use them.

Here every span that touches the gap is placed under the innermost span
holding it (its parent: the span that caused it), and the gap's coverage
is summed for each path of names from the outermost span down.  Walking
down from the outermost, the gap takes the deepest name whose path covers
at least half of it; where no child of a name does, the name itself.  A
gap that no program span touches is left to devtrace's naming.

This module reads traces only; ``devtrace.reduce`` does not call it, so
the benchmark's own ``breakdown.idle_gaps`` are named as devtrace names
them.  :func:`idle_gaps` gives the same gaps as ``devtrace.reduce`` with
the names this module gives where it can.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from . import devtrace

PROGRAM_PREFIXES = ("sim.", "fluid.")
SPAN_PREFIXES = (devtrace.HOST_SPAN_PREFIX,) + PROGRAM_PREFIXES

HostSpan = devtrace.HostSpan


def read_host_spans(path) -> List[HostSpan]:
    """The harness's and the program's host spans of one ``.xplane.pb``."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out: List[HostSpan] = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
    return out


class SpanIndex:
    """Host spans grouped by name, for finding those that touch an
    interval without a scan of the whole trace."""

    def __init__(self, spans: Sequence[HostSpan]) -> None:
        by_name: Dict[str, List[Tuple[float, float]]] = {}
        for name, s, e in spans:
            by_name.setdefault(name, []).append((s, e))
        self._groups = []
        for name, iv in by_name.items():
            iv.sort()
            longest = max(e - s for s, e in iv)
            self._groups.append((name, [s for s, _ in iv], iv, longest))

    def touching(self, lo: float, hi: float) -> List[Tuple[float, float, str]]:
        """``(start, end, name)`` of every span overlapping ``(lo, hi)``."""
        out = []
        for name, starts, iv, longest in self._groups:
            i0 = bisect.bisect_left(starts, lo - longest)
            i1 = bisect.bisect_left(starts, hi)
            out.extend((s, e, name) for s, e in iv[i0:i1] if e > lo)
        return out


def name_gap(index: SpanIndex, lo: float, hi: float) -> Optional[str]:
    """The deepest name whose path covers at least half of ``[lo, hi]``
    (see the module docstring); None where no program span touches it."""
    spans = index.touching(lo, hi)
    if not any(n.startswith(PROGRAM_PREFIXES) for _, _, n in spans):
        return None
    spans.sort(key=lambda t: (t[0], -t[1]))
    cover: Dict[tuple, float] = {}
    children: Dict[tuple, set] = {}
    stack: List[Tuple[float, tuple]] = []  # (end, path) of open spans
    for s, e, name in spans:
        while stack and (stack[-1][0] <= s or stack[-1][0] < e):
            stack.pop()  # ended before this span, or does not hold it
        parent = stack[-1][1] if stack else ()
        path = parent + (name,)
        children.setdefault(parent, set()).add(path)
        cover[path] = cover.get(path, 0.0) + min(e, hi) - max(s, lo)
        stack.append((e, path))
    half = 0.5 * (hi - lo)
    path: tuple = ()
    while True:
        best = max(children.get(path, ()), key=cover.__getitem__,
                   default=None)
        if best is None or cover[best] < half:
            break
        path = best
    return path[-1] if path else "host"


def idle_gaps(dev, host: Sequence[HostSpan], window: Tuple[float, float],
              n_devices: Optional[int] = None) -> List[Tuple[str, float]]:
    """``devtrace.reduce(...).gaps``, each renamed by :func:`name_gap`
    where a program span touches it."""
    harness = [h for h in host if h[0].startswith(devtrace.HOST_SPAN_PREFIX)]
    red = devtrace.reduce(dev, harness, window, n_devices)
    lo, hi = window
    holes: List[Tuple[float, float]] = []
    for d in sorted({d for d, *_ in dev}) or [0]:
        merged = devtrace._union(devtrace._clip(
            ((s, e) for dd, line, _, s, e in dev
             if dd == d and line == devtrace.OPS_LINE), lo, hi))
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                holes.append((edge, s))
            edge = max(edge, e)
    holes.sort(key=lambda g: g[0] - g[1])
    index = SpanIndex(host)
    out = []
    for (name, secs), (s, e) in zip(red.gaps, holes):
        out.append((name_gap(index, s, e) or name, secs))
    return out
