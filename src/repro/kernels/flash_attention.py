"""Pallas TPU flash attention (forward): causal / sliding-window / GQA.

Online-softmax over KV blocks with accumulators resident in VMEM. Grid:
(batch*q_heads, q_blocks, kv_blocks) — the kv axis is the innermost,
sequential ("arbitrary") dimension so the (m, l, acc) scratch carries across
kv steps. GQA is handled in the K/V index maps (q head -> kv head) so the
grouped KV never gets materialized at q-head width.

VMEM working set per program:
  q block (bq, d) + k/v blocks (bk, d) + scores (bq, bk) + acc (bq, d)
with bq = bk = 512 and d = 128 in bf16/f32 this is ~1.9 MB « 16 MB VMEM,
and every matmul dimension is a multiple of the 128-wide MXU.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      sm_scale: float, causal: bool, window: int,
                      block_q: int, block_k: int, n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # static-shape positions for masking
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        # skip blocks entirely in the future
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        # skip blocks entirely outside the attention window
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window > 0:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int = 0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)

    def kv_index(bh, iq, ik):
        bb = bh // h
        hh = bh % h
        return (bb * hkv + hh // g, ik, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
