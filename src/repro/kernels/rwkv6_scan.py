"""RWKV6 (Finch) recurrence as a chunked Pallas-TPU kernel.

TPU adaptation of the data-dependent-decay linear recurrence: the
(D_k x D_v) per-head state is the bandwidth hazard — a naive per-timestep
scan round-trips it through HBM T times (the XLA baseline in
models/blocks.py does exactly that, and the roofline memory term shows
it). Here the grid iterates (batch*head, chunk) with the chunk axis
sequential, so the state matrix stays RESIDENT IN VMEM across the whole
sequence; HBM traffic drops from O(T * D^2) to O(T * D + D^2).

Inside a chunk the recurrence is still stepped (fori_loop over the chunk)
— rank-1 state updates on the VPU; the intra-chunk matrix form (secondary
chunking with decay rescaling, as in flash-linear-attention) is the next
optimization recorded in EXPERIMENTS.md §Perf.

Validated in interpret mode against ``ref.rwkv6_reference``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
            chunk: int, n_chunks: int, d: int):
    cj = pl.program_id(1)

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)          # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, D) bonus row

    def step(t, carry):
        S, out = carry                        # S: (D, D) k-major
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)   # (1, D)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = kt.T @ vt                                   # (D, D)
        yt = rt @ (S + u.T * kv)                         # (1, D)
        S = wt.T * S + kv
        out = jax.lax.dynamic_update_slice_in_dim(out, yt, t, 0)
        return S, out

    S0 = state_ref[...]
    out0 = jnp.zeros((chunk, d), jnp.float32)
    S, out = jax.lax.fori_loop(0, chunk, step, (S0, out0))
    state_ref[...] = S
    o_ref[0] = out.astype(o_ref.dtype)


def rwkv6_chunked(r, k, v, w, u, *, chunk: int = 128,
                  interpret: Optional[bool] = None):
    """r,k,v,w: (B,H,T,D); u: (H,D). Returns y: (B,H,T,D) float32.

    T must be a multiple of ``chunk``. The state stays in VMEM across
    chunks (sequential minor grid dimension). ``interpret=None`` runs
    compiled on TPU and interpreted elsewhere
    (``kernels.ops.default_interpret``).
    """
    from .ops import resolve_interpret
    return _rwkv6_chunked(r, k, v, w, u, chunk=chunk,
                          interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _rwkv6_chunked(r, k, v, w, u, *, chunk: int, interpret: bool):
    b, h, t, d = r.shape
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    rf = r.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    wf = w.reshape(b * h, t, d)
    uf = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc, d=d)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d), lambda bh, j: (bh, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return out.reshape(b, h, t, d)
