"""Pallas TPU kernel for the RG-LRU linear recurrence (Griffin).

y_t = a_t * y_{t-1} + x_t over the sequence, blocked (B, S, W) ->
grid (b, w_blocks, s_blocks). The sequence axis is the innermost
("arbitrary") grid dimension so the carried state h lives in VMEM scratch
across sequence blocks; within a block the recurrence runs as a fori_loop
over rows of the VMEM-resident (block_s, block_w) tile.

This is the decode/training-friendly linear-depth form; the pure-jnp oracle
(ref.rg_lru_ref) and the model's associative_scan path are its references.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(a_ref, x_ref, y_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)  # (block_s, block_w)
    x = x_ref[0].astype(jnp.float32)

    def body(i, h):
        ai = jax.lax.dynamic_slice_in_dim(a, i, 1, axis=0)
        xi = jax.lax.dynamic_slice_in_dim(x, i, 1, axis=0)
        h = ai * h + xi  # (1, block_w)
        y_ref[pl.ds(0, 1), pl.ds(i, 1), :] = h.astype(y_ref.dtype)[None]
        return h

    h0 = h_ref[...][None, :] if h_ref.ndim == 1 else h_ref[...]
    h = jax.lax.fori_loop(0, block_s, body, h0.reshape(1, -1))
    h_ref[...] = h.reshape(h_ref.shape)


def rg_lru_pallas(
    a: jax.Array,  # (B, S, W) decay gates in (0, 1)
    x: jax.Array,  # (B, S, W) gated inputs
    *,
    block_s: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, s, w = x.shape
    block_s = min(block_s, s)
    block_w = min(block_w, w)
    assert s % block_s == 0 and w % block_w == 0, (s, w, block_s, block_w)
    ns, nw = s // block_s, w // block_w

    kernel = functools.partial(_rg_lru_kernel, block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid=(b, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si: (bi, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda bi, wi, si: (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((b, s, w), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)
    return out
