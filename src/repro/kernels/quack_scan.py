"""QUACK aggregation Pallas-TPU kernel — the protocol's compute hot loop.

Every round, every sender folds R receiver claim/complaint bitmaps over a
W-message window into stake-weighted quorum decisions (§4.1/§4.2):

    quacked[s,w] = sum_r stakes[r] * claims[s,r,w]     >= u_r + 1
    lost[s,w]    = sum_r stakes[r] * complaints[s,r,w] >= r_r + 1  & ~quacked
    prefix[s]    = length of the contiguous quacked prefix

At RSM scale (hundreds of replicas x 10^5-message windows x thousands of
link-pairs) this is a dense stake-weighted matmul + a prefix-AND scan —
MXU work. Grid: (senders, W/block); the claim/complaint tiles stream into
VMEM and the stake row is resident. The scalars live in SMEM: the two
thresholds, the per-sender prefix output, and the (alive, run) carry that
crosses window blocks. A block's contribution to the prefix is its first
unquacked column (an iota, a ``where`` and a ``min``), so no scan runs
inside the kernel.

Every operand keeps full-size trailing block dimensions (the (R, bw)
tiles, the (1, bw) output rows, the (1, 2) threshold pair, the (1, 1)
prefix cell), so the Mosaic tiling rule holds as is and under ``vmap``,
which prepends one squeezed block dimension per batch axis.

Checked against ``ref.quack_reference`` in interpret mode
(``tests/test_kernels.py``), compiled for a described v5e
(``tests/test_tpu_compile.py``), and against the jnp quorum path on the
chip (``chip_smoke.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# window block streamed per grid step — the single home of the kernel's
# alignment requirement (callers padding W to a block multiple import
# this, e.g. core.quack.stake_quorum_bitmap).
BLOCK_W = 512


def _kernel(thr_ref, stakes_ref, claims_ref, comp_ref, quacked_ref,
            lost_ref, prefix_ref, carry_ref):
    """One (sender, window block) step; ``comp_ref``/``lost_ref`` are
    None when the loss quorum is not computed."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[0] = jnp.int32(1)      # prefix still alive
        carry_ref[1] = jnp.int32(0)      # prefix length so far

    stakes = stakes_ref[...]                                   # (1, R)
    w_claim = jnp.dot(stakes, claims_ref[0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)      # (1, bw)
    quacked = w_claim >= thr_ref[0, 0]
    quacked_ref[0] = quacked
    if comp_ref is not None:
        w_comp = jnp.dot(stakes, comp_ref[0].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        lost_ref[0] = (w_comp >= thr_ref[0, 1]) & ~quacked

    bw = quacked.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, quacked.shape, 1)
    first = jnp.min(jnp.where(quacked, bw, col))   # first unquacked column
    alive = carry_ref[0]
    run = carry_ref[1] + alive * first
    carry_ref[0] = alive * (first == bw).astype(jnp.int32)
    carry_ref[1] = run
    prefix_ref[0, 0, 0] = run


def _kernel_no_lost(thr_ref, stakes_ref, claims_ref, quacked_ref,
                    prefix_ref, carry_ref):
    _kernel(thr_ref, stakes_ref, claims_ref, None, quacked_ref, None,
            prefix_ref, carry_ref)


def quack_scan(claims, complaints, stakes, quack_thresh, dup_thresh, *,
               block_w: int = BLOCK_W, interpret: Optional[bool] = None,
               compute_lost: bool = True):
    """claims/complaints: (S,R,W) bool; stakes: (R,) f32.

    Returns (quacked (S,W) bool, lost (S,W) bool, prefix (S,) int32).
    W must be a multiple of block_w (or smaller than it); on TPU the
    block is also a multiple of 128 or all of W.

    ``compute_lost=False`` drops the loss-quorum side entirely — the
    complaints operand is never streamed into VMEM and its stake matmul
    never issued (Pallas kernels are opaque to XLA DCE, so a dead
    output must be cut at the kernel boundary, not left for the
    compiler) — and ``lost`` comes back as ``None``.

    ``interpret=None`` runs the compiled kernel on TPU and the Pallas
    interpreter elsewhere (``kernels.ops.default_interpret``).
    """
    from .ops import resolve_interpret
    w = claims.shape[-1]
    if w % min(block_w, w):
        raise ValueError(f"window width {w} is not a multiple of the "
                         f"kernel block {block_w}")
    return _quack_scan(claims, complaints, stakes, quack_thresh, dup_thresh,
                       block_w=block_w,
                       interpret=resolve_interpret(interpret),
                       compute_lost=compute_lost)


@functools.partial(jax.jit,
                   static_argnames=("block_w", "interpret",
                                    "compute_lost"))
def _quack_scan(claims, complaints, stakes, quack_thresh, dup_thresh, *,
                block_w: int, interpret: bool, compute_lost: bool):
    s, r, w = claims.shape
    bw = min(block_w, w)
    stakes2 = stakes.reshape(1, r).astype(jnp.float32)
    thr = jnp.stack([jnp.asarray(quack_thresh, jnp.float32),
                     jnp.asarray(dup_thresh, jnp.float32)]).reshape(1, 2)

    thr_spec = pl.BlockSpec((1, 2), lambda i, j: (0, 0),
                            memory_space=pltpu.SMEM)
    row = pl.BlockSpec((1, r), lambda i, j: (0, 0))
    tile = pl.BlockSpec((1, r, bw), lambda i, j: (i, 0, j))
    out_w = pl.BlockSpec((1, 1, bw), lambda i, j: (i, 0, j))
    out_p = pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    bits = jax.ShapeDtypeStruct((s, 1, w), jnp.bool_)
    prefix = jax.ShapeDtypeStruct((s, 1, 1), jnp.int32)
    common = dict(
        grid=(s, w // bw),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="quack_scan",
    )
    if not compute_lost:
        quacked, pre = pl.pallas_call(
            _kernel_no_lost,
            in_specs=[thr_spec, row, tile],
            out_specs=[out_w, out_p],
            out_shape=[bits, prefix],
            **common,
        )(thr, stakes2, claims)
        return quacked[:, 0], None, pre[:, 0, 0]
    quacked, lost, pre = pl.pallas_call(
        _kernel,
        in_specs=[thr_spec, row, tile, tile],
        out_specs=[out_w, out_w, out_p],
        out_shape=[bits, bits, prefix],
        **common,
    )(thr, stakes2, claims, complaints)
    return quacked[:, 0], lost[:, 0], pre[:, 0, 0]
