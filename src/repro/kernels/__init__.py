"""Pallas TPU kernels for the perf-critical compute layers.

Kernels (each: <name>.py kernel body, ops.py jit wrapper, ref.py oracle):
  semijoin        -- blocked sort-merge membership probe
  semijoin(count) -- join multiplicity counting (match loop expansion)
  pair_semijoin   -- (s, o) pair membership (SPMD cycle-close probe)
  flash_attention -- causal/SWA/GQA blocked attention (LM stack)

Validated on CPU via interpret=True (``tests/test_kernels.py``); the
join kernels are compiled for a described TPU v5e by
``tests/test_tpu_compile.py`` and run on a chip by ``chip_smoke.py``.
"""
from .ops import attention, compact_rows, join_count, pair_semijoin, semijoin
from . import ref

__all__ = ["attention", "compact_rows", "join_count", "pair_semijoin",
           "semijoin", "ref"]
