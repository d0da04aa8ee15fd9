"""The check fails a run whose timed path is broken underneath.

Each test plants one fault in the program for the length of a tiny run
(the harness's look for a chip is skipped) and sees ``correct`` come out
false: a protocol step that returns its state unchanged, half of the
links left out of the session, and a QUACK round altered where the step
produces it (which leaves every count and frontier as it was, so only
the per-message comparison can see it). A run across chips has no
exchange to leave out: every cell runs on one chip.
"""

import jax.numpy as jnp
import pytest

import repro.core.simulator as sim
import repro.stream.session as session_mod
from tiny import run_tiny


@pytest.fixture
def fresh_programs():
    """Compiled programs are cached per spec; a planted fault must be
    traced anew, and must not leak into later tests."""
    def clear():
        sim._compiled_batch_chunk.cache_clear()
        sim._compiled_batch_superchunk.cache_clear()
    clear()
    yield
    clear()


def _wrap_step(monkeypatch, alter):
    real = sim._protocol_step

    def planted(spec, fail, sched_w, base, w):
        step = real(spec, fail, sched_w, base, w)
        return lambda state, t: alter(step, state, t)
    monkeypatch.setattr(sim, "_protocol_step", planted)


def test_step_returning_state_unchanged(monkeypatch, fresh_programs):
    def unchanged(step, state, t):
        _, metrics = step(state, t)
        return state, metrics
    _wrap_step(monkeypatch, unchanged)
    out = run_tiny("lan4_fleet")
    assert not out["correct"]
    # the step's own counts still read as if it had run; the frozen
    # state shows in the frontier and the per-message outcomes
    assert out["checks"]["frontier_diff"]["value"] > 0
    assert out["checks"]["message_diff"]["value"] > 0


def test_half_the_links_left_out(monkeypatch):
    real = session_mod.StreamSession._specs
    monkeypatch.setattr(session_mod.StreamSession, "_specs",
                        lambda self: real(self)[: len(real(self)) // 2])
    out = run_tiny("lan4_fleet", links=4)
    assert not out["correct"]
    assert out["checks"]["undelivered"]["value"] > 0
    assert out["checks"]["frontier_diff"]["value"] > 0


def test_quack_round_altered_where_produced(monkeypatch, fresh_programs):
    def late_quack(step, state, t):
        new, metrics = step(state, t)
        fresh = (state.quack_time < 0) & (new.quack_time >= 0)
        return new._replace(quack_time=jnp.where(
            fresh, new.quack_time + 1, new.quack_time)), metrics
    _wrap_step(monkeypatch, late_quack)
    out = run_tiny("geo19_fleet")
    assert not out["correct"]
    checks = out["checks"]
    assert checks["message_diff"]["value"] > 0
    assert checks["frontier_diff"]["value"] == 0
    assert checks["round_diff"]["value"] == 0
