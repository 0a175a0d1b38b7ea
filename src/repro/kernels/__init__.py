# Pallas TPU kernels for the compute hot-spots (DESIGN.md section 5):
#   flash_attention — training/prefill attention (causal / window / GQA)
#   metronome_score — the paper's Score-phase rotation enumeration (Eq. 18)
#   metronome_fill  — the fluid engine's batched progressive fill
#   rg_lru          — Griffin's linear recurrence
# Each has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py; off a
# TPU the wrappers run the kernels in interpret mode only when asked.
from . import ops, ref

__all__ = ["ops", "ref"]
