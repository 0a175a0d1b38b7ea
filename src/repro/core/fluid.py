"""Backend-swappable fluid rate engine (progressive-filling max-min fairness).

The rate-sharing core of the event-driven simulator, refactored out of
``ClusterSimulator`` so production-scale traces (10k+ jobs) can swap the
per-flow Python loop for a batched vectorized solve:

  * ``backend='python'`` — the seed's per-flow loop, verbatim, as the
    golden oracle: per-link water filling when every path is a single host
    link (the star topology), global progressive filling otherwise.
    Bit-for-bit identical to the historical ``ClusterSimulator`` path.
  * ``backend='jnp'`` — the fill expressed as a fixed point over a
    (flows x links) demand/route matrix, solved by the jit'd jnp oracle
    (``kernels.ref.progressive_fill_ref``), float32.
  * ``backend='kernel'`` — same matrix form through the ``metronome_fill``
    Pallas kernel (``kernels.ops.progressive_fill``): compiled on a TPU.
    Off a TPU it raises, unless the caller of :func:`fill_many` /
    :func:`fill_corpus` passes ``interpret=True`` (interpret mode, for
    parity tests); it never substitutes the jnp path.

The matrix form: routes[f, l] = 1 iff flow f's path crosses link l.  Each
round every unfrozen flow grows by the same increment — the minimum over
remaining per-flow headroom and remaining per-link capacity divided by the
link's active-flow count — and flows freeze when their demand is met or a
path link saturates.  This is exactly the per-flow loop's round structure,
so the vectorized backends agree with the oracle up to float32 tolerance.

Incremental recomputation rides the PR 5 epoch machinery: flows partition
into link-connected *affinity components* (two flows are connected when
their paths share a link), each component's allocation depends only on its
own demands and link capacities, and the engine memoizes per-component
solutions under a content key.  A dynamic-environment event (background
ramp, capacity change, departure) therefore re-fills only the component it
touches — the others hit the memo.  The python backend keeps incremental
mode OFF by default: the global progressive fill couples components through
the shared increment's float partial sums, so per-component solving is
equivalent mathematically but not bit-for-bit, and ``backend='python'``
must reproduce the seed exactly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EPS = 1e-9

BACKENDS = ("python", "jnp", "kernel")


# ---------------------------------------------------------------------------
# program spans and timers (SimConfig.profile)
# ---------------------------------------------------------------------------

def trace_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session records,
    else None.  Callers look it up once per run and hand it down, so a tick
    pays no check; jax is imported here and not with the module, so
    ``repro.core`` stays importable without it."""
    try:
        from jax import profiler
    except ImportError:
        return None
    annotation = profiler.TraceAnnotation
    return annotation if annotation.is_enabled() else None


_UNTIMED = contextlib.nullcontext()


class _Interval:
    __slots__ = ("stats", "field", "span", "t0")

    def __init__(self, stats, field: str, span) -> None:
        self.stats, self.field, self.span = stats, field, span

    # the span's own cost falls inside the interval, so that a parent's
    # time outside its timed parts holds no tracing cost
    def __enter__(self) -> None:
        self.t0 = time.perf_counter()
        if self.span is not None:
            self.span.__enter__()

    def __exit__(self, *exc) -> None:
        if self.span is not None:
            self.span.__exit__(*exc)
        dt = time.perf_counter() - self.t0
        setattr(self.stats, self.field, getattr(self.stats, self.field) + dt)


def interval(stats, field: str, annotate, name: str):
    """Context manager that adds the seconds of its body to
    ``stats.<field>`` and, where ``annotate`` (see :func:`trace_annotation`)
    is given, spans the body as ``name`` on the profiler's clock.  With
    ``stats=None`` it does nothing and allocates nothing."""
    if stats is None:
        return _UNTIMED
    return _Interval(stats, field,
                     annotate(name) if annotate is not None else None)


# ---------------------------------------------------------------------------
# golden oracle: the seed's per-flow loop, verbatim
# ---------------------------------------------------------------------------

def _progressive_fill(
    demands: np.ndarray,
    paths: Sequence[Sequence[str]],
    caps: Dict[str, float],
) -> np.ndarray:
    """Progressive-filling max-min fairness over multi-link flow paths.

    All unfrozen flows grow at the same rate; a flow freezes when it reaches
    its demand or when any link on its path saturates (that link becomes its
    bottleneck). Reduces to per-link water filling when every path is a
    single link. Runs in O((flows + links) * flows).
    """
    n = len(demands)
    rates = np.zeros(n)
    if n == 0:
        return rates
    remaining = dict(caps)
    active = [i for i in range(n) if demands[i] > EPS]
    # flows on a zero-capacity link can never send
    while active:
        counts: Dict[str, int] = {}
        for i in active:
            for l in paths[i]:
                counts[l] = counts.get(l, 0) + 1
        inc = min(demands[i] - rates[i] for i in active)
        for l, c in counts.items():
            inc = min(inc, remaining[l] / c)
        inc = max(0.0, inc)
        for i in active:
            rates[i] += inc
        for l, c in counts.items():
            remaining[l] -= inc * c
        nxt = []
        for i in active:
            if rates[i] >= demands[i] - EPS:
                continue  # demand met
            if any(remaining[l] <= EPS for l in paths[i]):
                continue  # bottleneck link saturated
            nxt.append(i)
        if len(nxt) == len(active):  # pragma: no cover — defensive
            break
        active = nxt
    return rates


def _max_min_fair(demands: np.ndarray, capacity: float) -> np.ndarray:
    """Water-filling max-min fair allocation, each flow capped at its demand."""
    n = len(demands)
    if n == 0:
        return demands
    if demands.sum() <= capacity:
        return demands.copy()
    rates = np.zeros(n)
    remaining = capacity
    order = np.argsort(demands)
    left = n
    for idx in order:
        fair = remaining / left
        give = min(demands[idx], fair)
        rates[idx] = give
        remaining -= give
        left -= 1
    return rates


def fill_python(
    demands: np.ndarray,
    paths: Sequence[Tuple[str, ...]],
    caps: Dict[str, float],
) -> np.ndarray:
    """The golden-oracle solve of one fill problem (float64, per-flow loop).

    Mirrors the seed's rate-assignment dispatch exactly: all-single-link
    problems take the per-link water-filling fast path, anything else the
    global progressive fill."""
    demands = np.asarray(demands, dtype=float)
    if all(len(p) == 1 for p in paths):
        rates = np.zeros(len(demands))
        by_link: Dict[str, List[int]] = {}
        for i, p in enumerate(paths):
            by_link.setdefault(p[0], []).append(i)
        for link_id, idxs in by_link.items():
            sub = _max_min_fair(demands[idxs], caps[link_id])
            for i, r in zip(idxs, sub):
                rates[i] = float(r)
        return rates
    return _progressive_fill(demands, paths, caps)


# ---------------------------------------------------------------------------
# (flows x links) matrix form
# ---------------------------------------------------------------------------

def problem_matrix(
    demands: Sequence[float],
    paths: Sequence[Tuple[str, ...]],
    caps: Dict[str, float],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, List[str]]:
    """Build the (flows x links) demand/route matrix of one fill problem.

    Links are ordered by first appearance over the flows' paths, so the
    matrix is deterministic for a given flow ordering.  Returns
    ``(demands (F,), routes (F, L), cap_vec (L,), link_ids)``."""
    link_ids: List[str] = []
    index: Dict[str, int] = {}
    for p in paths:
        for l in p:
            if l not in index:
                index[l] = len(link_ids)
                link_ids.append(l)
    f, l = len(paths), len(link_ids)
    routes = np.zeros((f, max(l, 1)), dtype=np.float32)
    for i, p in enumerate(paths):
        for lid in p:
            routes[i, index[lid]] = 1.0
    d = np.asarray(demands, dtype=np.float32)
    cap_vec = np.asarray([caps[lid] for lid in link_ids] or [1.0],
                         dtype=np.float32)
    return d, routes, cap_vec, link_ids


@dataclasses.dataclass
class CorpusStats:
    """Bucket occupancy / padding waste of batched corpus fills.

    Every :func:`fill_corpus` call with ``stats=`` accumulates how many
    (flow, link) matrix slots it actually dispatched versus how many were
    real problem content, so batching losses are visible per run instead of
    silent (ISSUE 7 satellite): ``occupancy`` near 1.0 means the buckets are
    tight; a low value means shape rounding / batch padding dominates."""

    calls: int = 0      # fill_corpus invocations
    problems: int = 0   # real problems solved (excl. batch-padding dummies)
    buckets: int = 0    # batched dispatches (fill_many calls)
    flow_used: int = 0  # real flow slots across all problems
    flow_slots: int = 0  # dispatched flow slots (B_pad x F_pad summed)
    link_used: int = 0
    link_slots: int = 0

    @property
    def flow_occupancy(self) -> float:
        return self.flow_used / self.flow_slots if self.flow_slots else 1.0

    @property
    def link_occupancy(self) -> float:
        return self.link_used / self.link_slots if self.link_slots else 1.0

    def as_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["flow_occupancy"] = self.flow_occupancy
        d["link_occupancy"] = self.link_occupancy
        return d


def _round_pow2(n: int, floor: int = 4) -> int:
    """Smallest power of two >= max(n, floor) (jit-cache shape bucketing)."""
    p = floor
    while p < n:
        p <<= 1
    return p


def fill_many(
    problems: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    pad_to: Optional[Tuple[int, int]] = None,
    timing: Optional["FluidStats"] = None,
    annotate=None,
) -> List[np.ndarray]:
    """Solve many fill problems in ONE batched dispatch.

    ``problems``: a list of ``(demands (F_i,), routes (F_i, L_i), caps
    (L_i,))`` matrices (see :func:`problem_matrix`).  Problems are padded to
    a common (B, F_max, L_max) block — zero-demand flows never activate and
    zero-route unit-capacity links never saturate, so padding is neutral —
    and solved by the vectorized backend in a single call.  Returns the
    unpadded per-problem rate vectors.

    ``interpret=True`` runs the ``'kernel'`` backend's Pallas kernel in
    interpret mode (off a TPU; see the module docstring).

    ``pad_to=(F, L)`` raises the pad shape beyond the batch maximum so
    repeated calls with similar problems land on a fixed set of jit-compiled
    shapes (the event-loop steady state) instead of recompiling per tick.

    This is the production-trace throughput path: thousands of active-set
    snapshots of a 10k-job trace fill together instead of one per-flow
    Python loop each (``benchmarks/bench_trace_throughput.py``).

    ``timing`` (a :class:`FluidStats`) books padding and unpadding as
    ``pack_s`` and the fill, until its rates are on the host, as
    ``device_s``; ``annotate`` spans them (see :func:`interval`)."""
    if backend not in ("jnp", "kernel"):
        raise ValueError(f"fill_many wants a vectorized backend, got {backend!r}")
    if not problems:
        return []
    from repro.kernels import ops as kops  # deferred: core stays jax-free

    with interval(timing, "pack_s", annotate, "fluid.pack"):
        b = len(problems)
        f_max = max(max(p[0].shape[0] for p in problems), 1)
        l_max = max(max(p[2].shape[0] for p in problems), 1)
        if pad_to is not None:
            f_max = max(f_max, int(pad_to[0]))
            l_max = max(l_max, int(pad_to[1]))
        d = np.zeros((b, f_max), dtype=np.float32)
        routes = np.zeros((b, f_max, l_max), dtype=np.float32)
        caps = np.ones((b, l_max), dtype=np.float32)
        for i, (di, ri, ci) in enumerate(problems):
            fi, li = ri.shape
            d[i, :fi] = di
            routes[i, :fi, :li] = ri
            caps[i, :li] = ci
    with interval(timing, "device_s", annotate, "fluid.device"):
        if backend == "jnp":
            out = kops.progressive_fill_ref(d, routes, caps)
        else:
            out = kops.progressive_fill(d, routes, caps, interpret=interpret)
    with interval(timing, "pack_s", annotate, "fluid.pack"):
        return [np.asarray(out[i, : p[0].shape[0]], dtype=float)
                for i, p in enumerate(problems)]


def fill_corpus(
    problems: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
    *,
    backend: str = "jnp",
    interpret: Optional[bool] = None,
    chunk: int = 64,
    bucket_shapes: bool = False,
    stats: Optional[CorpusStats] = None,
    timing: Optional["FluidStats"] = None,
    annotate=None,
) -> List[np.ndarray]:
    """Solve a large, ragged fill-problem corpus with size-bucketed batches.

    :func:`fill_many` pads every problem to the corpus-wide ``(F_max,
    L_max)``, so one 1200-flow peak snapshot makes every off-peak snapshot
    pay 1200-flow einsums.  Here problems are sorted by flow count and
    dispatched in ``chunk``-sized buckets (each padded only to its own
    maximum), which keeps the padding waste near zero on diurnal traces
    where the active set swings several-fold.  Results come back in the
    caller's order.

    ``bucket_shapes=True`` additionally rounds every bucket's (B, F, L) up
    to fixed sizes (full ``chunk`` batches, power-of-two flow/link counts)
    so a long-lived caller — the simulator's event loop re-solving dirty
    components every tick — cycles through a handful of compiled shapes
    instead of jit-recompiling whenever the active set grows by one flow.
    Batch padding uses neutral dummy problems (one zero-demand flow).

    ``stats`` (a :class:`CorpusStats`) accumulates bucket occupancy /
    padding waste so the batching losses are observable per run.
    ``timing`` and ``annotate`` book this function's host work as
    ``pack_s``, as :func:`fill_many` does."""
    if not problems:
        return []
    with interval(timing, "pack_s", annotate, "fluid.pack"):
        order = sorted(range(len(problems)),
                       key=lambda i: problems[i][0].shape[0])
        out: List[Optional[np.ndarray]] = [None] * len(problems)
        chunk = max(1, int(chunk))
        if stats is not None:
            stats.calls += 1
            stats.problems += len(problems)
            stats.flow_used += sum(p[0].shape[0] for p in problems)
            stats.link_used += sum(p[2].shape[0] for p in problems)
        dummy = (np.zeros(1, dtype=np.float32),
                 np.zeros((1, 1), dtype=np.float32),
                 np.ones(1, dtype=np.float32))
    for s in range(0, len(order), chunk):
        with interval(timing, "pack_s", annotate, "fluid.pack"):
            idx = order[s:s + chunk]
            batch = [problems[i] for i in idx]
            pad_to = None
            if bucket_shapes:
                pad_to = (_round_pow2(max(p[0].shape[0] for p in batch)),
                          _round_pow2(max(p[2].shape[0] for p in batch)))
                batch = batch + [dummy] * (chunk - len(batch))
        rates = fill_many(batch, backend=backend, interpret=interpret,
                          pad_to=pad_to, timing=timing, annotate=annotate)
        with interval(timing, "pack_s", annotate, "fluid.pack"):
            if stats is not None:
                stats.buckets += 1
                f_pad = pad_to[0] if pad_to else max(
                    max(p[0].shape[0] for p in batch), 1)
                l_pad = pad_to[1] if pad_to else max(
                    max(p[2].shape[0] for p in batch), 1)
                stats.flow_slots += len(batch) * f_pad
                stats.link_slots += len(batch) * l_pad
            for i, r in zip(idx, rates):
                out[i] = r
    return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# incremental re-fill
# ---------------------------------------------------------------------------

def _first_seen_links(paths: Sequence[Tuple[str, ...]]) -> List[str]:
    """Link ids in first-appearance order over the flows' paths (the
    deterministic link ordering of memo keys and problem matrices)."""
    seen = set()
    out: List[str] = []
    for p in paths:
        for l in p:
            if l not in seen:
                seen.add(l)
                out.append(l)
    return out


@dataclasses.dataclass
class FluidStats:
    """Memo counters of one engine (incremental re-fill observability) and,
    while ``FluidEngine.timed`` is on, the seconds of ``solve_batch``:
    ``batch_s`` its whole body; ``key_s`` content keys, memo lookups and
    memo stores; ``pack_s`` the host side of the fill (problem matrices,
    sorting, dummies, padding, unpadding); ``device_s`` from the call into
    the fill until its rates are on the host, the one part in which the
    device works."""

    hits: int = 0
    misses: int = 0
    batch_s: float = 0.0
    key_s: float = 0.0
    pack_s: float = 0.0
    device_s: float = 0.0


class FluidEngine:
    """Backend-swappable progressive-filling engine.

    ``solve_batch(problems)`` solves the simulator's ``(demands, paths,
    caps)`` fill problems — one per dirty affinity component of a tick —
    in one dispatch; ``fill`` solves one problem without the memo.

    ``incremental=None`` picks the backend default: OFF for ``python``
    (the simulator's python path does not call ``solve_batch``; see the
    module docstring) and ON for the vectorized backends, where each
    affinity component's solution is memoized under a content key of its
    demands, paths and link capacities.  An event that touches one
    component leaves every other component's key — and therefore its
    memoized rates — intact."""

    def __init__(self, backend: str = "python",
                 incremental: Optional[bool] = None,
                 memo_max: int = 4096) -> None:
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown fluid backend {backend!r}; expected one of {BACKENDS}")
        self.backend = backend
        self.incremental = (backend != "python") if incremental is None \
            else bool(incremental)
        self.memo_max = int(memo_max)
        self._memo: Dict[tuple, np.ndarray] = {}
        self.stats = FluidStats()
        self.corpus_stats = CorpusStats()
        # FluidStats' timers run while ``timed`` is on; ``annotate`` (a
        # TraceAnnotation, see trace_annotation) also spans what they time
        self.timed = False
        self.annotate = None
        # oracle-parity sampling (bench_dynamic_throughput): with
        # sample_stride > 0 every stride-th solve_batch problem is kept as
        # (demands, paths, caps, rates) for offline fill_python comparison
        self.sample_stride = 0
        self.sample_max = 512
        self.samples: List[tuple] = []
        self._sample_seen = 0

    # ------------------------------------------------------------- public API
    def fill(self, demands: np.ndarray, paths: Sequence[Tuple[str, ...]],
             caps: Dict[str, float]) -> np.ndarray:
        """Solve one fill problem with this engine's backend (no memo)."""
        if self.backend == "python":
            return fill_python(np.asarray(demands, dtype=float), paths, caps)
        d, routes, cap_vec, _ = problem_matrix(demands, paths, caps)
        return fill_many([(d, routes, cap_vec)], backend=self.backend)[0]

    def solve_batch(self, problems: Sequence[tuple]) -> List[np.ndarray]:
        """Solve many ``(demands, paths, caps)`` problems in ONE dispatch.

        The event loop's dirty-component path: every dirty affinity
        component of one tick arrives here together; memoized components
        (content key: demands, paths, link capacities) return instantly,
        and ALL misses go through a single shape-bucketed
        :func:`fill_corpus` batch — one jit dispatch per tick instead of
        one per component.  Returns per-problem rate vectors in caller
        order.  Returned arrays are shared with the memo: treat as
        read-only."""
        timing = self.stats if self.timed else None
        ann = self.annotate if timing is not None else None
        if ann is not None:
            batch_span = ann("fluid.solve_batch")
            batch_span.__enter__()
        if timing is not None:
            t_start = time.perf_counter()
            if ann is not None:
                key_span = ann("fluid.key")
                key_span.__enter__()
        out: List[Optional[np.ndarray]] = [None] * len(problems)
        keys: List[Optional[tuple]] = [None] * len(problems)
        miss: List[int] = []
        for i, (demands, paths, caps) in enumerate(problems):
            if self.incremental:
                key = (self.backend,
                       tuple((float(d), tuple(p))
                             for d, p in zip(demands, paths)),
                       tuple(caps[l] for l in _first_seen_links(paths)))
                keys[i] = key
                hit = self._memo.get(key)
                if hit is not None:
                    self.stats.hits += 1
                    out[i] = hit
                    continue
            miss.append(i)
        if timing is not None:
            if ann is not None:
                key_span.__exit__(None, None, None)
            timing.key_s += time.perf_counter() - t_start
        if miss:
            self.stats.misses += len(miss)
            if self.backend == "python":
                for i in miss:
                    d, p, c = problems[i]
                    out[i] = fill_python(np.asarray(d, dtype=float), p, c)
            else:
                with interval(timing, "pack_s", ann, "fluid.pack"):
                    mats = [problem_matrix(*problems[i])[:3] for i in miss]
                rates = fill_corpus(mats, backend=self.backend,
                                    bucket_shapes=True,
                                    stats=self.corpus_stats,
                                    timing=timing, annotate=ann)
                for i, r in zip(miss, rates):
                    out[i] = r
            if self.incremental:
                with interval(timing, "key_s", ann, "fluid.key"):
                    for i in miss:
                        if len(self._memo) >= self.memo_max:
                            self._memo.clear()
                        self._memo[keys[i]] = out[i]
        if self.sample_stride > 0:
            for i, prob in enumerate(problems):
                self._sample_seen += 1
                if (self._sample_seen % self.sample_stride == 0
                        and len(self.samples) < self.sample_max):
                    self.samples.append((*prob, out[i]))
        if timing is not None:
            timing.batch_s += time.perf_counter() - t_start
        if ann is not None:
            batch_span.__exit__(None, None, None)
        return out  # type: ignore[return-value]
