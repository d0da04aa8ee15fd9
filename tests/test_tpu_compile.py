"""Compile-only checks for a described TPU v5e chip: no chip is used.

The TPU compiler refuses here what interpret mode accepts — block shapes
off the (8, 128) tiling, scalars stored to vector memory, operations with
no Mosaic lowering — so these compiles guard the kernels and the engine
program at real shapes: the QUACK kernel at n = 19 and a 1,024-message
window, the quorum wrapper's padding path, and the superchunk program
at n = 19, W = 1024, 32 lanes with and without the kernel.

The topology is described inside a fixture (never while a module is
imported), and the persistent compilation cache is off around these
compiles: what they write could not be read back without a chip.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import RSMConfig, SimConfig
from repro.core.quack import stake_quorum_bitmap
from repro.core.simulator import build_spec, superchunk_program
from repro.kernels import ops
from repro.kernels.quack_scan import quack_scan

N, W, LANES = 19, 1024, 32
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Code that asks the platform sees the CPU here; steer it to the
    compiled kernel, as it runs on the chip."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("compute_lost", [True, False])
def test_quack_scan_compiles(one_chip, compute_lost):
    bits = jax.ShapeDtypeStruct((N, N, W), jnp.bool_)
    args = _on(one_chip, (bits, bits,
                          jax.ShapeDtypeStruct((N,), jnp.float32),
                          jax.ShapeDtypeStruct((), jnp.float32),
                          jax.ShapeDtypeStruct((), jnp.float32)))
    fn = jax.jit(lambda c, d, s, q, r: quack_scan(
        c, d, s, q, r, interpret=False, compute_lost=compute_lost))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_stake_quorum_bitmap_pads_and_compiles(one_chip, compiled_kernels):
    w = 192                           # not a multiple of the 128-lane block
    bits = jax.ShapeDtypeStruct((N, N, w), jnp.bool_)
    args = _on(one_chip, (bits, bits,
                          jax.ShapeDtypeStruct((N,), jnp.float32)))
    fn = jax.jit(lambda c, d, s: stake_quorum_bitmap(
        c, d, s, 7.0, 7.0, use_pallas=True))
    lowered = fn.lower(*args)
    quacked, lost, prefix = lowered.out_info
    assert quacked.shape == lost.shape == (N, w) and prefix.shape == (N,)
    assert "tpu_custom_call" in lowered.compile().as_text()


def _superchunk_spec(use_pallas: bool):
    cluster = RSMConfig.bft(6)
    sim = SimConfig(n_msgs=8 * W, steps=2 * W + 96, window=4, phi=6,
                    window_slots=W, chunk_steps=16, superchunk=8)
    spec = build_spec(cluster, cluster, sim)
    assert (spec.n_s, spec.n_r, spec.window_slots) == (N, N, W)
    return dataclasses.replace(spec, use_pallas_quack=use_pallas)


def _compile_superchunk(one_chip, use_pallas: bool):
    program, args = superchunk_program(_superchunk_spec(use_pallas), LANES)
    compiled = program.lower(*_on(one_chip, args)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    return compiled.as_text()


def test_superchunk_compiles_jnp_quorum(one_chip):
    assert "tpu_custom_call" not in _compile_superchunk(one_chip, False)


def test_superchunk_compiles_pallas_quorum(one_chip, compiled_kernels):
    assert "tpu_custom_call" in _compile_superchunk(one_chip, True)
