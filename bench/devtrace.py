"""From a profiler trace to device busy time, program time and idle gaps.

The trace is reduced in two steps.  :func:`read_xplane` turns the
profiler's ``.xplane.pb`` into plain event tuples; :func:`reduce` works on
those tuples only, so that it can be checked on a small recorded trace
(``bench/tests/data/``) without a chip.

- Device events are those of the ``XLA Ops`` line of each ``/device:TPU:n``
  plane; busy time is the union of their intervals inside the window,
  averaged over the devices.
- A device op belongs to the program (``XLA Modules`` event) whose interval
  holds its midpoint; a program's time is the busy union of its ops.
- An idle gap is a stretch of the window in which no op runs on a device.
  It is named by the host span (``bench.admit``, ``bench.solve``: the
  harness's ``TraceAnnotation``) that covers most of it; by
  ``bench.chunk`` (the event loop outside those calls) where none does.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
TOP = 10  # entries of each breakdown list
CONTAINERS = ("bench.window", "bench.chunk")

# (device, line, name, start_ns, end_ns)
DeviceEvent = Tuple[int, str, str, float, float]
# (name, start_ns, end_ns)
HostSpan = Tuple[str, float, float]


def read_xplane(path) -> Tuple[List[DeviceEvent], List[HostSpan]]:
    """Device events of the op and module lines, and the harness's host
    spans, of one ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    dev: List[DeviceEvent] = []
    host: List[HostSpan] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            kind = _line_kind(line.name) if m is not None else None
            if kind is not None:
                for ev in line.events:
                    dev.append((int(m.group(1)), kind, ev.name,
                                float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
            elif m is None and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
    return dev, host


def _line_kind(name: str) -> Optional[str]:
    """``XLA Ops`` / ``XLA Modules``, or a line whose name says the same."""
    low = name.lower()
    if name == OPS_LINE or low.endswith(" ops"):
        return OPS_LINE
    if name == MODULES_LINE or low.endswith(" modules"):
        return MODULES_LINE
    return None


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def program_name(module_event: str) -> str:
    """``jit_metronome_fill(12)`` -> ``jit_metronome_fill``."""
    return module_event.split("(", 1)[0].strip()


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over devices
    devices: int
    program_s: Dict[str, float]        # summed over devices
    op_s: Dict[str, float]             # summed over devices
    gaps: List[Tuple[str, float]]      # (host activity, seconds), longest

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]


def reduce(dev: Sequence[DeviceEvent], host: Sequence[HostSpan],
           window: Tuple[float, float],
           n_devices: Optional[int] = None) -> Reduction:
    """Busy time, per-program and per-op time and idle gaps of the device
    events inside ``window`` (ns).  A window in which no device op ran is
    an error: either the trace's layout is not the one read here, or the
    run never reached the device."""
    lo, hi = window
    if not any(line == OPS_LINE and min(e, hi) > max(s, lo)
               for _, line, _, s, e in dev):
        raise ValueError(
            f"no device op in the traced window ({len(dev)} device events "
            "in the trace); the run never reached the device, or the "
            "trace's planes and lines are not the ones devtrace reads")
    ids = sorted({d for d, *_ in dev})
    n = n_devices or max(len(ids), 1)
    busy = 0.0
    program_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    spans = sorted((s, e, name) for name, s, e in host)
    for d in ids or [0]:
        ops = [(s, e, name) for dd, line, name, s, e in dev
               if dd == d and line == OPS_LINE]
        mods = sorted((s, e, program_name(name)) for dd, line, name, s, e
                      in dev if dd == d and line == MODULES_LINE)
        starts = [ms for ms, _, _ in mods]
        merged = _union(_clip(((s, e) for s, e, _ in ops), lo, hi))
        busy += sum(e - s for s, e in merged)
        per_prog: Dict[str, List[Tuple[float, float]]] = {}
        for s, e, name in ops:
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            op_s[name] = op_s.get(name, 0.0) + (ce - cs) * 1e-9
            mid = 0.5 * (s + e)
            k = bisect.bisect_right(starts, mid) - 1
            if k >= 0 and mid <= mods[k][1]:
                per_prog.setdefault(mods[k][2], []).append((cs, ce))
        for prog, iv in per_prog.items():
            program_s[prog] = program_s.get(prog, 0.0) + sum(
                e - s for s, e in _union(iv)) * 1e-9
        edge = lo
        for s, e in merged + [[hi, hi]]:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_activity(spans, s, e), (e - s) * 1e-9)
             for s, e in gaps[:TOP]]
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9,
                     devices=n, program_s=program_s, op_s=op_s, gaps=named)


def _activity(spans, lo: float, hi: float) -> str:
    """The harness span that covers most of ``[lo, hi]``; the window and
    chunk spans, which hold everything, name a gap only where no other
    span touches it."""
    best, best_cover = "host", 0.0
    outer, outer_cover = None, 0.0
    for s, e, name in spans:
        if s >= hi:
            break
        cover = min(e, hi) - max(s, lo)
        if cover <= 0:
            continue
        if name in CONTAINERS:
            if cover > outer_cover:
                outer, outer_cover = name, cover
        elif cover > best_cover:
            best, best_cover = name, cover
    if best_cover == 0.0 and outer is not None:
        return outer
    return best
