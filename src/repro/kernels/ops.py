"""jit'd public wrappers around the Pallas kernels.

Every dispatcher runs its Pallas kernel and nothing else: compiled on a
TPU, in interpret mode off a TPU only when the caller passes
``interpret=True``, and otherwise it raises an error naming the platform.
No dispatcher substitutes the jnp reference; that is the fluid engine's
``backend='jnp'`` (:func:`progressive_fill_ref`) under its own name.
:data:`DISPATCHES` counts each call by ``(op, mode)``, so a caller can show
which path ran.  Training gets a ``custom_vjp`` whose backward recomputes
through the jnp oracle (flash forward is exact, so gradients match the
reference path).
"""
from __future__ import annotations

import collections
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .flash_attention import flash_attention_fwd
from .metronome_fill import metronome_fill
from .metronome_score import (metronome_score_multilink,
                              metronome_score_multilink_batch,
                              metronome_score_pairwise)
from .rg_lru import rg_lru_pallas

# (op, "compiled" | "interpret") -> calls
DISPATCHES: collections.Counter = collections.Counter()
_DISPATCH_LOCK = threading.Lock()


def _interpret(op: str, interpret: Optional[bool]) -> bool:
    """The ``interpret`` flag for one Pallas call of ``op``: True when the
    caller asks for interpret mode, False on a TPU; anywhere else an error
    (never a silent fallback)."""
    if interpret:
        mode = "interpret"
    else:
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise RuntimeError(
                f"{op}: the compiled Pallas kernel needs a TPU, but JAX's "
                f"default platform is {platform!r}; pass interpret=True for "
                "interpret mode, or use the jnp reference in kernels.ref")
        mode = "compiled"
    with _DISPATCH_LOCK:
        DISPATCHES[(op, mode)] += 1
    return mode == "interpret"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    interpret: Optional[bool] = None):
    """(B,H,S,D) x (B,Hkv,S,D)^2 -> (B,H,S,D)."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=_interpret("flash_attention",
                                                    interpret))


def _fa_fwd(q, k, v, causal, window, interpret):
    out = flash_attention(q, k, v, causal, window, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                             window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# metronome rotation scoring
# ---------------------------------------------------------------------------

_score_pairwise_jit = jax.jit(metronome_score_pairwise,
                              static_argnames=("capacity", "interpret"))
_score_multilink_jit = jax.jit(metronome_score_multilink,
                               static_argnames="interpret")
_score_multilink_batch_jit = jax.jit(metronome_score_multilink_batch,
                                     static_argnames="interpret")


def score_pairwise(base_demand, bank_a, bank_b, capacity: float,
                   interpret: Optional[bool] = None) -> np.ndarray:
    """Eq. 18 scores for every (rot_a, rot_b) pair; see core/rotation.py."""
    out = _score_pairwise_jit(
        jnp.asarray(base_demand), jnp.asarray(bank_a), jnp.asarray(bank_b),
        capacity=float(capacity),
        interpret=_interpret("score_pairwise", interpret))
    return np.asarray(out)


def score_multilink(base_demand, bank_a, bank_b, capacities,
                    interpret: Optional[bool] = None) -> np.ndarray:
    """Joint (min-over-links) Eq. 18 scores for every rotation pair of two
    free jobs over stacked (L, R, S) per-link demand banks."""
    out = _score_multilink_jit(
        jnp.asarray(base_demand), jnp.asarray(bank_a), jnp.asarray(bank_b),
        jnp.asarray(capacities),
        interpret=_interpret("score_multilink", interpret))
    return np.asarray(out)


def score_multilink_batch(base_demand, bank_a, bank_b, capacities,
                          interpret: Optional[bool] = None) -> np.ndarray:
    """Candidate-batched joint Eq. 18 scores: ONE dispatch over stacked
    (C, L, R, S) banks returning (C, Ra, Rb) — the Score phase's surviving
    candidates evaluated together instead of one kernel launch each."""
    out = _score_multilink_batch_jit(
        jnp.asarray(base_demand), jnp.asarray(bank_a), jnp.asarray(bank_b),
        jnp.asarray(capacities),
        interpret=_interpret("score_multilink_batch", interpret))
    return np.asarray(out)


# ---------------------------------------------------------------------------
# progressive-filling fluid solve
# ---------------------------------------------------------------------------

_progressive_fill_ref_jit = jax.jit(ref.progressive_fill_ref)
_progressive_fill_jit = jax.jit(metronome_fill, static_argnames="interpret")


def progressive_fill_ref(demands, routes, caps) -> np.ndarray:
    """The jit'd jnp fixed-point fill — the fluid engine's ``backend='jnp'``
    path, on whatever platform JAX runs."""
    return np.asarray(_progressive_fill_ref_jit(
        jnp.asarray(demands), jnp.asarray(routes), jnp.asarray(caps)))


def progressive_fill(demands, routes, caps,
                     interpret: Optional[bool] = None) -> np.ndarray:
    """Batched progressive-fill rates (B, F) over (B, F, L) route matrices
    through the ``metronome_fill`` kernel — the fluid engine's
    ``backend='kernel'`` path."""
    out = _progressive_fill_jit(
        jnp.asarray(demands), jnp.asarray(routes), jnp.asarray(caps),
        interpret=_interpret("progressive_fill", interpret))
    return np.asarray(out)


# ---------------------------------------------------------------------------
# rg-lru recurrence
# ---------------------------------------------------------------------------

def rg_lru(a, x, interpret: Optional[bool] = None):
    return rg_lru_pallas(a, x, interpret=_interpret("rg_lru", interpret))
