"""Pallas TPU kernels for Metronome's rotation-scheme scoring (Eq. 18).

The paper calls the Score phase "computationally intensive" (section III-B):
for every candidate rotation scheme, sum the bandwidth demand over the
discretized circle and measure the excess over link capacity. We adapt the
enumeration to the TPU as a *pairwise* product core: two free tasks' rolled
banks (Ra, S) and (Rb, S) are resident in VMEM and a (block_a x Rb x S)
broadcast-accumulate + relu-reduce produces a block of the (Ra, Rb) score
matrix per grid step. Outer tasks (if any) are folded into ``base_demand``
by the caller (repro.core.rotation holds all but the innermost two fixed —
the paper's own reduction argument).

:func:`metronome_score_multilink` extends the pairwise core to the
fabric-wide joint solve (``core/rotation.py``): the demand banks are
stacked per link — ``(L, Ra, S)`` / ``(L, Rb, S)`` with per-link capacities
— and the relu-excess is reduced over links *and* slots in one kernel.  The
joint score of a rotation pair is the worst per-link Eq. 18 score
(feasible iff every link is perfect), computed as the max over links of the
normalized excess fraction.

The slot axis S (Di-Pre = 72) is padded to the 128-wide TPU lane dimension;
padded slots carry zero demand so they never contribute excess.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _score_kernel(base_ref, bank_a_ref, bank_b_ref, out_ref, *,
                  capacity: float, n_slots: int, block_a: int, rb: int):
    base = base_ref[...]           # (1, S_pad)
    bank_a = bank_a_ref[...]       # (block_a, S_pad)
    bank_b = bank_b_ref[...]       # (Rb, S_pad)
    # total[a, b, s] = base[s] + bank_a[a, s] + bank_b[b, s]
    total = (base[None, :, :] + bank_a[:, None, :] + bank_b[None, :, :]
             )  # (block_a, Rb, S_pad)
    excess = jnp.maximum(total - capacity, 0.0)
    ex = jnp.sum(excess, axis=-1)  # (block_a, Rb)
    score = jnp.maximum(0.0, 100.0 * (1.0 - ex / (capacity * n_slots)))
    out_ref[...] = score.astype(out_ref.dtype)


def metronome_score_pairwise(
    base_demand: jax.Array,  # (S,)
    bank_a: jax.Array,  # (Ra, S)
    bank_b: jax.Array,  # (Rb, S)
    capacity: float,
    *,
    block_a: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Scores (Ra, Rb) for every rotation pair of two free tasks."""
    s = base_demand.shape[-1]
    ra, rb = bank_a.shape[0], bank_b.shape[0]
    s_pad = -(-s // LANE) * LANE
    ra_pad = -(-ra // block_a) * block_a

    def pad(x, rows):
        out = jnp.zeros((rows, s_pad), jnp.float32)
        return out.at[: x.shape[0], :s].set(x.astype(jnp.float32))

    base = pad(base_demand[None, :], 1)
    a = pad(bank_a, ra_pad)
    b = pad(bank_b, rb)

    kernel = functools.partial(_score_kernel, capacity=float(capacity),
                               n_slots=s, block_a=block_a, rb=rb)
    out = pl.pallas_call(
        kernel,
        grid=(ra_pad // block_a,),
        in_specs=[
            pl.BlockSpec((1, s_pad), lambda i: (0, 0)),
            pl.BlockSpec((block_a, s_pad), lambda i: (i, 0)),
            pl.BlockSpec((rb, s_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, rb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ra_pad, rb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(base, a, b)
    return out[:ra, :rb]


def _multilink_kernel(caps_ref, base_ref, bank_a_ref, bank_b_ref, out_ref, *,
                      n_slots: int, n_links: int):
    caps = caps_ref[...]           # (L, LANE) — capacity broadcast per lane
    base = base_ref[...]           # (L, 1, S_pad)
    bank_a = bank_a_ref[...]       # (L, block_a, S_pad)
    bank_b = bank_b_ref[...]       # (L, Rb, S_pad)
    cap_col = caps[:, :1]          # (L, 1)
    # total[l, a, b, s] = base[l, s] + bank_a[l, a, s] + bank_b[l, b, s]
    total = (base[:, :, None, :] + bank_a[:, :, None, :]
             + bank_b[:, None, :, :])  # (L, block_a, Rb, S_pad)
    excess = jnp.maximum(total - cap_col[:, None, :, None], 0.0)
    ex = jnp.sum(excess, axis=-1)  # (L, block_a, Rb) — reduce over slots
    # per-link normalized excess fraction, then reduce over links: the worst
    # link dominates (min over per-link scores == 100 * (1 - max frac))
    frac = ex / (cap_col[:, None, :] * n_slots)
    worst = jnp.max(frac, axis=0)  # (block_a, Rb)
    score = jnp.maximum(0.0, 100.0 * (1.0 - worst))
    out_ref[...] = score.astype(out_ref.dtype)


def _multilink_batch_kernel(caps_ref, base_ref, bank_a_ref, bank_b_ref,
                            out_ref, *, n_slots: int):
    caps = caps_ref[...][0]        # (L, LANE) — one candidate's capacities
    base = base_ref[...][0]        # (L, 1, S_pad)
    bank_a = bank_a_ref[...][0]    # (L, block_a, S_pad)
    bank_b = bank_b_ref[...][0]    # (L, Rb, S_pad)
    cap_col = caps[:, :1]          # (L, 1)
    total = (base[:, :, None, :] + bank_a[:, :, None, :]
             + bank_b[:, None, :, :])  # (L, block_a, Rb, S_pad)
    excess = jnp.maximum(total - cap_col[:, None, :, None], 0.0)
    ex = jnp.sum(excess, axis=-1)  # (L, block_a, Rb)
    frac = ex / (cap_col[:, None, :] * n_slots)
    worst = jnp.max(frac, axis=0)  # (block_a, Rb)
    score = jnp.maximum(0.0, 100.0 * (1.0 - worst))
    out_ref[...] = score[None].astype(out_ref.dtype)


def metronome_score_multilink_batch(
    base_demand: jax.Array,  # (C, L, S) fixed demand per candidate and link
    bank_a: jax.Array,  # (C, L, Ra, S)
    bank_b: jax.Array,  # (C, L, Rb, S)
    capacities: jax.Array,  # (C, L)
    *,
    block_a: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Joint scores (C, Ra, Rb) for EVERY candidate in one dispatch.

    The Score phase's one-shot batched evaluation: each of the C surviving
    candidate placements of a pod contributes its own stacked per-link
    demand banks and capacities, and the grid walks (candidate, Ra-block)
    pairs so a single kernel launch replaces the historical per-candidate
    ``metronome_score_multilink`` calls.  Candidates with fewer links are
    padded with zero-demand unit-capacity links, which score a constant 100
    and cannot change the min-over-links."""
    c, l, s = base_demand.shape
    ra, rb = bank_a.shape[2], bank_b.shape[2]
    s_pad = -(-s // LANE) * LANE
    ra_pad = -(-ra // block_a) * block_a

    def pad(x, rows):
        out = jnp.zeros((c, l, rows, s_pad), jnp.float32)
        return out.at[:, :, : x.shape[2], :s].set(x.astype(jnp.float32))

    base = pad(base_demand[:, :, None, :], 1)
    a = pad(bank_a, ra_pad)
    b = pad(bank_b, rb)
    caps = jnp.broadcast_to(
        jnp.asarray(capacities, jnp.float32)[:, :, None], (c, l, LANE))

    kernel = functools.partial(_multilink_batch_kernel, n_slots=s)
    out = pl.pallas_call(
        kernel,
        grid=(c, ra_pad // block_a),
        in_specs=[
            pl.BlockSpec((1, l, LANE), lambda ci, i: (ci, 0, 0)),
            pl.BlockSpec((1, l, 1, s_pad), lambda ci, i: (ci, 0, 0, 0)),
            pl.BlockSpec((1, l, block_a, s_pad), lambda ci, i: (ci, 0, i, 0)),
            pl.BlockSpec((1, l, rb, s_pad), lambda ci, i: (ci, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_a, rb), lambda ci, i: (ci, i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, ra_pad, rb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(caps, base, a, b)
    return out[:, :ra, :rb]


def metronome_score_multilink(
    base_demand: jax.Array,  # (L, S) fixed demand per link
    bank_a: jax.Array,  # (L, Ra, S)
    bank_b: jax.Array,  # (L, Rb, S)
    capacities: jax.Array,  # (L,)
    *,
    block_a: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Joint scores (Ra, Rb): min over links of Eq. 18 for every rotation
    pair of two free jobs, all links evaluated in one kernel.

    Links where a job is absent carry zero rows in its bank; padded slots
    carry zero demand — neither can contribute excess."""
    l, s = base_demand.shape
    ra, rb = bank_a.shape[1], bank_b.shape[1]
    s_pad = -(-s // LANE) * LANE
    ra_pad = -(-ra // block_a) * block_a

    def pad(x, rows):
        out = jnp.zeros((l, rows, s_pad), jnp.float32)
        return out.at[:, : x.shape[1], :s].set(x.astype(jnp.float32))

    base = pad(base_demand[:, None, :], 1)
    a = pad(bank_a, ra_pad)
    b = pad(bank_b, rb)
    caps = jnp.broadcast_to(
        jnp.asarray(capacities, jnp.float32)[:, None], (l, LANE))

    kernel = functools.partial(_multilink_kernel, n_slots=s, n_links=l)
    out = pl.pallas_call(
        kernel,
        grid=(ra_pad // block_a,),
        in_specs=[
            pl.BlockSpec((l, LANE), lambda i: (0, 0)),
            pl.BlockSpec((l, 1, s_pad), lambda i: (0, 0, 0)),
            pl.BlockSpec((l, block_a, s_pad), lambda i: (0, i, 0)),
            pl.BlockSpec((l, rb, s_pad), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_a, rb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ra_pad, rb), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(caps, base, a, b)
    return out[:ra, :rb]
