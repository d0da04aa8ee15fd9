"""Flash attention Pallas-TPU kernel (causal + sliding-window + GQA).

TPU-native adaptation of the flash algorithm: the grid iterates
(batch*q_head, q_block, kv_block) with the kv dimension 'arbitrary'
(sequential) so the online-softmax running state (m, l, acc) lives in VMEM
scratch across kv steps; q/k/v tiles stream HBM->VMEM through BlockSpecs.
Block shapes default to (128, 128) — MXU-aligned (128x128 systolic array),
and the working set  bq*D + bkv*D * 2 + bq*bkv  stays well under VMEM.

Validated on CPU in interpret mode against ``ref.mha_reference``
(tests/test_kernels.py sweeps shapes/dtypes/window/causal).
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bq: int, bkv: int, n_kv_blocks: int,
            causal: bool, window: int, q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bkv, D)
    v = v_ref[0].astype(jnp.float32)                    # (bkv, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)

    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0)
    k_pos = kj * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    ok = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B,H,Sq,D); k,v: (B,KV,Skv,D) -> (B,H,Sq,D).

    Sq and Skv must be multiples of the block sizes; D should be a
    multiple of 128 for MXU alignment (any D works in interpret mode).
    ``interpret=None`` runs compiled on TPU and interpreted elsewhere
    (``kernels.ops.default_interpret``).
    """
    from .ops import resolve_interpret
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_kv=block_kv,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def _flash_attention(q, k, v, *, causal: bool, window: int, block_q: int,
                     block_kv: int, interpret: bool):
    b, h, sq, d = q.shape
    _, n_kv, skv, _ = k.shape
    g = h // n_kv
    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    nq, nkv = sq // bq, skv // bkv
    q_offset = skv - sq

    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * n_kv, skv, d)
    vf = v.reshape(b * n_kv, skv, d)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d), bq=bq, bkv=bkv, n_kv_blocks=nkv,
        causal=causal, window=window, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, i, j, g=g: (bh // g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda bh, i, j, g=g: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)
