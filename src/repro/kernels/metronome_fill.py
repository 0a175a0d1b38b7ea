"""Pallas TPU kernel for the batched progressive-filling fluid solve.

``core/fluid.py`` reduces max-min fair rate sharing to a fixed point over a
(flows x links) demand/route matrix; this kernel runs that fixed point for
a whole batch of fill problems — one grid step per problem, the per-round
state (rates, remaining capacity, active mask) resident in VMEM.  It is the
``backend='kernel'`` path of the fluid engine and the throughput core of
``benchmarks/bench_trace_throughput.py``, where thousands of active-set
snapshots of a 10k-job production trace fill in one dispatch.

Shape discipline mirrors ``metronome_score_multilink``: the link axis is
padded to the 128-wide TPU lane dimension and the flow axis to the sublane
multiple; padded flows carry zero demand (never activate) and padded links
carry zero routes with unit capacity (never saturate), so padding cannot
perturb the fixed point.  Each round freezes at least one flow of every
unfinished problem, so the in-kernel loop is bounded by the padded flow
count; parity with ``ref.progressive_fill_ref`` is exercised in interpret
mode by the tier-1 suite (``tests/test_fluid.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import FILL_EPS

LANE = 128
SUBLANE = 8
_INF = 1e30


def _fill_kernel(demands_ref, routes_ref, caps_ref, out_ref, *, f_pad: int):
    d = demands_ref[...][0]        # (F_pad, 1)
    routes = routes_ref[...][0]    # (F_pad, L_pad)
    caps = caps_ref[...][0]        # (1, L_pad)

    act0 = (d > FILL_EPS).astype(jnp.float32)
    state0 = (jnp.zeros_like(d), caps, act0)

    def body(_, state):
        rates, rem, act = state
        counts = jnp.sum(routes * act, axis=0, keepdims=True)  # (1, L_pad)
        ratio = jnp.where(counts > 0.5,
                          rem / jnp.maximum(counts, 1.0), _INF)
        head = jnp.where(act > 0.5, d - rates, _INF)
        inc = jnp.maximum(jnp.minimum(jnp.min(ratio), jnp.min(head)), 0.0)
        inc = jnp.where(jnp.any(act > 0.5), inc, 0.0)  # drained problem
        rates = rates + inc * act
        rem = rem - inc * counts
        sat = (rem <= FILL_EPS).astype(jnp.float32)    # (1, L_pad)
        blocked = jnp.max(routes * sat, axis=1, keepdims=True) > 0.5
        met = rates >= d - FILL_EPS
        act = jnp.where(jnp.logical_or(met, blocked), 0.0, act)
        return rates, rem, act

    rates, _, _ = jax.lax.fori_loop(0, f_pad + 1, body, state0)
    out_ref[...] = rates[None].astype(out_ref.dtype)


def metronome_fill(
    demands: jax.Array,  # (B, F) per-flow demand caps
    routes: jax.Array,   # (B, F, L) 0/1 route matrix
    caps: jax.Array,     # (B, L) per-link capacities
    *,
    interpret: bool = False,
) -> jax.Array:
    """Batched progressive-fill rates (B, F), one grid step per problem."""
    b, f = demands.shape
    l = routes.shape[-1]
    f_pad = -(-f // SUBLANE) * SUBLANE
    l_pad = -(-l // LANE) * LANE

    d = jnp.zeros((b, f_pad, 1), jnp.float32)
    d = d.at[:, :f, 0].set(demands.astype(jnp.float32))
    r = jnp.zeros((b, f_pad, l_pad), jnp.float32)
    r = r.at[:, :f, :l].set(routes.astype(jnp.float32))
    # padded links: unit capacity, zero routes — they never saturate.  The
    # singleton middle axis makes the block's last two dims (1, L_pad) equal
    # the array's, which the TPU lowering requires for any batch size.
    c = jnp.ones((b, 1, l_pad), jnp.float32)
    c = c.at[:, 0, :l].set(caps.astype(jnp.float32))

    kernel = functools.partial(_fill_kernel, f_pad=f_pad)
    out = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, f_pad, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, f_pad, l_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, l_pad), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, f_pad, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, f_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(d, r, c)
    return out[:, :f, 0]
