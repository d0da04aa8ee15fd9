"""The protocol step reads its per-replica tables with no gather.

A TPU runs a gather of per-slot indices one index at a time, so the
step looks up the retransmission rotations (``rs_seq``/``rr_seq``), the
per-pair drops and the sender masks elementwise. These tests hold the
lookups to plain numpy fancy indexing, lane by lane under ``vmap``, and
hold the compiled windowed step to having no ``gather`` in the phases
that do them.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxprlint import iter_scoped_eqns
from repro.core import FailureScenario, RSMConfig, SimConfig
from repro.core.simulator import (_retransmit_lookups, _send_lookups,
                                  build_spec, superchunk_program)

# (name, sender, receiver, SimConfig kwargs): the identity rotations of
# unit stakes and staked DSS rotations of 4·n entries, the longest
# build_spec makes
STAKED_4 = RSMConfig(n=4, u=1, r=1, stakes=(1., 10., 10., 10.))
STAKED_7 = RSMConfig(n=7, u=2, r=2,
                     stakes=(1., 9., 9., 9., 9., 9., 9.))
DSS = dict(scheduler="dss", quantum=12)
SPECS = [
    ("unit_bft1", RSMConfig.bft(1), RSMConfig.bft(1), {}),
    ("unit_bft6", RSMConfig.bft(6), RSMConfig.bft(6), {}),
    ("staked_sender", STAKED_4, RSMConfig.bft(1), DSS),
    ("staked_both_4_to_7", STAKED_4, STAKED_7, DSS),
]
IDS = [s[0] for s in SPECS]


def _spec(snd, rcv, kw, n_msgs=64):
    sim = SimConfig(n_msgs=n_msgs, steps=n_msgs // 4 + 40, window=1, phi=6,
                    window_slots=32, chunk_steps=4, **kw)
    return build_spec(snd, rcv, sim, FailureScenario.none())


@pytest.mark.parametrize("name,snd,rcv,kw", SPECS, ids=IDS)
def test_lookups_equal_fancy_indexing(name, snd, rcv, kw):
    spec = _spec(snd, rcv, kw)
    n_s, n_r = spec.n_s, spec.n_r
    ls, lr = len(spec.rs_seq), len(spec.rr_seq)
    if name.startswith("unit"):
        assert spec.rs_seq == tuple(range(n_s))
        assert spec.rr_seq == tuple(range(n_r))
    else:
        assert ls == 4 * n_s and spec.rs_seq != tuple(range(ls))
        if name.endswith("4_to_7"):
            assert lr == 4 * n_r
    rng = np.random.default_rng(sum(map(ord, name)))
    b, w = 5, 48
    base = rng.integers(0, 10_000, size=b).astype(np.int32)
    abs_idx = (base[:, None] + np.arange(w, dtype=np.int32)).astype(np.int32)
    # retry counts well past both tables' lengths
    retry = rng.integers(0, 3 * max(ls, lr) + 5,
                         size=(b, n_s, w)).astype(np.int32)
    orig_sender = rng.integers(0, n_s, size=(b, w)).astype(np.int32)
    orig_recv = rng.integers(0, n_r, size=(b, w)).astype(np.int32)
    drop_pair = rng.random((b, n_s, n_r)) < 0.3
    alive_s = rng.random((b, n_s)) < 0.7
    byz_send_drop = rng.random((b, n_s)) < 0.3

    elected, hot, drop = jax.vmap(
        lambda dp, a, o, r: _retransmit_lookups(spec, dp, a, o, r))(
            drop_pair, abs_idx, orig_recv, retry)
    s_hot, r_hot, ok, drop_o = jax.vmap(_send_lookups)(
        alive_s & ~byz_send_drop, drop_pair, orig_sender, orig_recv)

    rs, rr = np.asarray(spec.rs_seq), np.asarray(spec.rr_seq)
    lane = np.arange(b)[:, None]
    sender = rs[(abs_idx[:, None, :] + retry) % ls]
    target = rr[(orig_recv[:, None, :] + retry) % lr]
    np.testing.assert_array_equal(
        elected, sender == np.arange(n_s)[None, :, None])
    np.testing.assert_array_equal(
        hot, target[:, :, None, :] == np.arange(n_r)[None, None, :, None])
    np.testing.assert_array_equal(
        drop, np.take_along_axis(drop_pair, target, axis=2))
    np.testing.assert_array_equal(
        s_hot, orig_sender[:, None, :] == np.arange(n_s)[None, :, None])
    np.testing.assert_array_equal(
        r_hot, orig_recv[:, None, :] == np.arange(n_r)[None, :, None])
    np.testing.assert_array_equal(
        ok, alive_s[lane, orig_sender] & ~byz_send_drop[lane, orig_sender])
    np.testing.assert_array_equal(
        drop_o, drop_pair[lane, orig_sender, orig_recv])


def _gathers_by_scope(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [(scope, set(re.findall(r"c3b_[a-z]+", scope)))
            for scope, eqn in iter_scoped_eqns(jaxpr)
            if eqn.primitive.name == "gather"]


def test_scoped_walk_sees_a_gather_inside_a_nested_jit():
    """The walk composes name stacks across sub-jaxprs: ``take_along_axis``
    stages its gather inside a jit, which alone names no scope."""
    def fn(table, idx):
        with jax.named_scope("c3b_retransmit"):
            return jnp.take_along_axis(table, idx, axis=1)

    found = _gathers_by_scope(
        jax.vmap(fn), jnp.zeros((2, 4, 4), bool),
        jnp.zeros((2, 4, 8), jnp.int32))
    assert [names for _, names in found] == [{"c3b_retransmit"}]


@pytest.mark.parametrize("name,snd,rcv,kw",
                         [SPECS[0], SPECS[3]], ids=[IDS[0], IDS[3]])
def test_windowed_step_has_no_gather_in_retransmit_or_send(name, snd, rcv,
                                                           kw):
    program, args = superchunk_program(_spec(snd, rcv, kw), lanes=2)
    bad = [scope for scope, names in _gathers_by_scope(program, *args)
           if names & {"c3b_retransmit", "c3b_send"}]
    assert not bad, bad
