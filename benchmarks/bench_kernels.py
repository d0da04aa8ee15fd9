"""Kernel micro-benchmarks: us/call for each Pallas kernel vs its pure-jnp
oracle. The kernels run as the platform picks
(``repro.kernels.ops.default_interpret``): compiled on a TPU, through the
Pallas interpreter elsewhere, where the times say nothing about the
chip. Each line names the mode it ran in."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, quack_scan, rwkv6_chunked
from repro.kernels.ops import default_interpret
from repro.kernels.ref import (mha_reference, quack_reference,
                               rwkv6_reference)


def mode() -> str:
    """How the kernels run on this platform: "interpreted" or "compiled"."""
    return "interpreted" if default_interpret() else "compiled"


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main():
    tag = mode()
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)

    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    t_kern = _time(lambda *a: flash_attention(*a, causal=True, block_q=128,
                                              block_kv=128), q, k, v)
    t_ref = _time(lambda *a: mha_reference(*a, causal=True), q, k, v)
    print(f"flash_attention_{tag},{t_kern:.0f},ref_us={t_ref:.0f}")

    r = jax.random.normal(ks[0], (1, 2, 256, 32)) * 0.5
    kk = jax.random.normal(ks[1], (1, 2, 256, 32)) * 0.5
    vv = jax.random.normal(ks[2], (1, 2, 256, 32)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 2, 256, 32))) * .5 + .45
    u = jax.random.normal(ks[4], (2, 32)) * 0.5
    t_kern = _time(lambda *a: rwkv6_chunked(*a, chunk=128), r, kk, vv, w, u)
    t_ref = _time(lambda *a: rwkv6_reference(*a)[0], r, kk, vv, w, u)
    print(f"rwkv6_chunked_{tag},{t_kern:.0f},ref_us={t_ref:.0f}")

    claims = jax.random.bernoulli(ks[0], 0.6, (4, 16, 1024))
    comps = jax.random.bernoulli(ks[1], 0.2, (4, 16, 1024))
    stakes = jnp.ones(16)
    t_kern = _time(lambda *a: quack_scan(*a, 5.0, 2.0, block_w=512),
                   claims, comps, stakes)
    t_ref = _time(lambda *a: quack_reference(*a, 5.0, 2.0),
                  claims, comps, stakes)
    print(f"quack_scan_{tag},{t_kern:.0f},ref_us={t_ref:.0f}")


if __name__ == "__main__":
    main()
