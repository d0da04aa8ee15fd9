"""Pallas TPU kernels for the perf-critical compute layers.

kernel               | hot-spot                        | oracle
---------------------|--------------------------------|---------------------
flash_attention      | attention (all dense/MoE/VLM)   | ref.mha_reference
rwkv6_scan           | RWKV6 data-dependent recurrence | ref.rwkv6_reference
quack_scan           | QUACK quorum aggregation (S4)   | ref.quack_reference
"""

from . import ref
from .ops import flash_attention, quack_scan, rwkv6_chunked

__all__ = ["flash_attention", "rwkv6_chunked", "quack_scan", "ref"]
