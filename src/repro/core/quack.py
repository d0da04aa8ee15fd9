"""QUACK (cumulative quorum acknowledgement) primitives (§4.1, §5.1).

All functions are pure jnp array ops so they can run inside ``lax.scan``
(simulator) or be jit-compiled standalone. Sequence numbers are 0-based and
acks are *counts*: ``ack == p`` means "I hold the contiguous prefix of p
messages m_0 .. m_{p-1}". A QUACK for prefix p forms at a sender once
replicas totalling ``u_r + 1`` stake have acked >= p — at least one of those
is honest, and an honest receiver broadcasts intra-RSM, so delivery of
m_0..m_{p-1} is guaranteed (§4.1 "Detecting successful sends").

Sliding-window (offset-aware) form: every function takes an optional
``base`` — the absolute sequence number of column 0 of the ``received``
array. The window invariant maintained by the simulator's GC rotation
(§4.3) is that everything below ``base`` is already held (or floor-acked)
by every replica whose acks still matter, so the absolute cumulative ack
is ``base +`` the in-window prefix and gap ranks start at zero at the
window base. ``base == 0`` with a full-width array recovers the dense
semantics exactly.

``base`` may be a python int, a traced scalar (device-side window
rotation carries it as scan state), or a per-scenario batch of scalars
under ``jax.vmap`` (batched windowed sweeps) — all offset arithmetic is
normalized to int32 so the three instantiations produce bit-identical
programs.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "cumulative_ack",
    "claim_bitmask",
    "weighted_quorum_prefix",
    "selective_quack",
    "missing_below_horizon",
    "stake_quorum_bitmap",
]


def stake_quorum_bitmap(claims: jnp.ndarray, complaints: jnp.ndarray,
                        stakes: jnp.ndarray, quack_thresh: float,
                        dup_thresh: float, *, use_pallas: bool = False,
                        need_lost: bool = True):
    """Stake-weighted QUACK / loss quorum decisions over a window (§4.1/§4.2).

    claims / complaints: (n_s, n_r, W) bool — receiver claim and
    repeat-complaint bitmaps as known to each sender. Returns
    ``(quacked (n_s, W) bool, lost (n_s, W) bool, prefix (n_s,) int32)``
    where ``quacked`` is the u_r+1 stake quorum, ``lost`` the r_r+1
    duplicate-complaint quorum on not-yet-quacked messages, and
    ``prefix`` the contiguous quacked prefix length (window-relative; the
    caller adds its window ``base``).

    ``use_pallas`` routes the reduction through the Pallas TPU kernel
    (``kernels.quack_scan`` — MXU stake matmul + cross-block prefix
    carry; compiled on TPU, interpret mode elsewhere via
    ``kernels.ops.default_interpret``).
    Stakes are small integers in every configuration the protocol uses,
    so the float32 quorum sums are exact and the two paths agree
    bit-for-bit (``tests/test_pipeline.py``).

    ``need_lost=False`` declares the loss quorum unused (``lost`` comes
    back ``None``): the jnp path's complaints einsum would be DCE'd by
    XLA anyway, but a Pallas kernel is opaque to DCE, so the kernel path
    must drop the complaints stream at the call boundary.
    """
    stakes = stakes.astype(jnp.float32)
    if use_pallas:
        from ..kernels.quack_scan import BLOCK_W, quack_scan
        # the kernel streams W in blocks of BLOCK_W, or of all of W
        # below that, and the chip's tiling wants lane-dense blocks (a
        # multiple of 128 columns); window widths are arbitrary (auto
        # sizing rounds to 64, growth doubles, dense fallback uses M),
        # so pad with never-claimed columns — they sit beyond every
        # real column, leaving the quorum bitmaps and the contiguous
        # quacked prefix untouched — and slice back.
        w = claims.shape[-1]
        pad = (-w) % min(BLOCK_W, -(-w // 128) * 128)
        if pad:
            ext = jnp.zeros(claims.shape[:-1] + (pad,), dtype=bool)
            claims = jnp.concatenate([claims, ext], axis=-1)
            complaints = jnp.concatenate([complaints, ext], axis=-1)
        # thresholds stay jnp values (possibly traced — stake re-weight
        # swaps feed them through FailArrays): the kernel takes them as
        # an SMEM operand, so a traced threshold costs no recompile
        quacked, lost, prefix = quack_scan(
            claims, complaints, stakes,
            jnp.asarray(quack_thresh, dtype=jnp.float32),
            jnp.asarray(dup_thresh, dtype=jnp.float32), block_w=BLOCK_W,
            compute_lost=need_lost)
        return (quacked[..., :w],
                None if lost is None else lost[..., :w],
                prefix.astype(jnp.int32))
    w_claim = jnp.einsum("ljm,j->lm", claims.astype(jnp.float32), stakes)
    quacked = w_claim >= quack_thresh
    lost = None
    if need_lost:
        w_comp = jnp.einsum("ljm,j->lm", complaints.astype(jnp.float32),
                            stakes)
        lost = (w_comp >= dup_thresh) & ~quacked
    prefix = jnp.sum(jnp.cumprod(quacked.astype(jnp.int32), axis=-1),
                     axis=-1)
    return quacked, lost, prefix.astype(jnp.int32)


def cumulative_ack(received: jnp.ndarray, base=0) -> jnp.ndarray:
    """Highest contiguous prefix count per receiver.

    received: (n_r, W) bool -> (n_r,) int32 *absolute* counts. ``base`` is
    the absolute index of column 0 (window invariant: everything below it
    counts as received).
    """
    base = jnp.asarray(base, dtype=jnp.int32)
    prefix = jnp.cumprod(received.astype(jnp.int32), axis=-1)
    return (base + prefix.sum(axis=-1)).astype(jnp.int32)


def missing_below_horizon(received: jnp.ndarray, phi: int,
                          base=0) -> jnp.ndarray:
    """Which messages a receiver reports missing, bounded by the phi-list.

    A receiver only reports gaps below its highest received index (anything
    above could simply not have been sent yet), and at most ``phi`` of them
    (§4.2 Parallel Cumulative Acknowledgments). Returns (n_r, W) bool for
    the window columns; gaps can only exist at or above ``base``.
    """
    w = received.shape[-1]
    base = jnp.asarray(base, dtype=jnp.int32)
    idx = base + jnp.arange(w, dtype=jnp.int32)
    # top[j] = 1 + highest received index (base if nothing in-window)
    any_recv = received.any(axis=-1)
    top = jnp.where(any_recv,
                    base + w - jnp.argmax(received[..., ::-1], axis=-1),
                    base).astype(jnp.int32)
    missing = (~received) & (idx[None, :] < top[:, None])
    # keep only the first `phi` missing entries per row
    rank = jnp.cumsum(missing.astype(jnp.int32), axis=-1)
    return missing & (rank <= phi)


def claim_bitmask(received: jnp.ndarray, phi: int, base=0, total=None):
    """Receiver's honest ack payload: (cum_ack, claim, claim_known).

    claim_known[j, k] — the ack message from j describes the status of k
    (true for all k below the horizon where <= phi gaps exist);
    claim[j, k]      — j claims to have received k (only meaningful where
    claim_known).  This is exactly "cumulative counter + phi-list" in array
    form: below the horizon, claim == received; missing list = the gaps.

    ``base``/``total`` select the sliding-window form: columns cover
    absolute indices [base, base + W) of a stream of ``total`` messages
    (``total`` must be given explicitly when ``base`` is traced).
    """
    w = received.shape[-1]
    base = jnp.asarray(base, dtype=jnp.int32)
    if total is None:
        total = base + w
    total = jnp.asarray(total, dtype=jnp.int32)
    idx = base + jnp.arange(w, dtype=jnp.int32)
    cum = cumulative_ack(received, base)
    # horizon: everything strictly below the (phi+1)-th missing index is
    # described. rank counts missing entries; positions with rank <= phi and
    # (missing => in the reported list) are known.
    missing_all = (~received)
    rank_all = jnp.cumsum(missing_all.astype(jnp.int32), axis=-1)
    # (phi+1)-th missing position per row (or `total` if <= phi gaps)
    over = rank_all > phi
    horizon = jnp.where(over.any(axis=-1),
                        base + jnp.argmax(over, axis=-1), total)
    # also bounded by top (we cannot claim receipt of unseen suffix): known
    # region = [0, max(horizon, cum)) union received-with-rank<=phi.
    known = idx[None, :] < horizon[:, None]
    claim = received & known
    # everything below cum is received by definition of cum:
    claim = claim | (idx[None, :] < cum[:, None])
    known = known | (idx[None, :] < cum[:, None])
    return cum, claim, known


def weighted_quorum_prefix(ack_vals: jnp.ndarray, stakes: jnp.ndarray,
                           threshold: float) -> jnp.ndarray:
    """Largest prefix p such that stake >= threshold has acked >= p (§5.1).

    ack_vals: (..., n_r) int; stakes: (n_r,); returns (...,) int32.
    Sort acks descending, accumulate stake, and take the largest ack value
    at which the running stake first reaches the threshold.
    """
    order = jnp.argsort(-ack_vals, axis=-1)
    sorted_acks = jnp.take_along_axis(ack_vals, order, axis=-1)
    sorted_stakes = jnp.take_along_axis(
        jnp.broadcast_to(stakes, ack_vals.shape), order, axis=-1)
    cw = jnp.cumsum(sorted_stakes, axis=-1)
    ok = cw >= threshold
    idx = jnp.argmax(ok, axis=-1)  # first position where quorum reached
    val = jnp.take_along_axis(sorted_acks, idx[..., None], axis=-1)[..., 0]
    return jnp.where(ok.any(axis=-1), val, 0).astype(jnp.int32)


def selective_quack(known_has: jnp.ndarray, stakes: jnp.ndarray,
                    threshold: float) -> jnp.ndarray:
    """Per-message QUACK with phi-list info (§4.2 parallel recovery).

    known_has: (..., n_r, M) bool — sender's knowledge that receiver j claims
    to hold message k. Returns (..., M) bool: stake-weighted count >= u_r+1.
    """
    w = jnp.einsum("...jm,j->...m", known_has.astype(stakes.dtype), stakes)
    return w >= threshold
