"""Public entry points of the Pallas kernels and their platform choice.

Each kernel takes ``interpret=None`` by default, which resolves here:
on a TPU the kernel runs compiled (Mosaic), on any other backend the
Pallas interpreter runs the kernel body instead (bit-faithful to the
TPU algorithm, slow). Passing ``interpret`` explicitly overrides that.
"""

from __future__ import annotations

from typing import Optional

import jax

from .flash_attention import flash_attention
from .quack_scan import quack_scan
from .rwkv6_scan import rwkv6_chunked

__all__ = ["flash_attention", "rwkv6_chunked", "quack_scan",
           "on_tpu", "default_interpret", "resolve_interpret"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A caller's ``interpret`` choice, or the platform's when None."""
    return default_interpret() if interpret is None else bool(interpret)
