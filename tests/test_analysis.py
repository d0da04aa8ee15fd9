"""repro.analysis: AST linter rules, jaxpr auditor, runtime sanitizer.

Each AST rule gets a minimal fixture snippet that triggers *exactly one*
finding (and a twin suppressed with ``# analysis: ignore[rule]``); the
jaxpr auditor is run over a tiny windowed config and must certify the
engine's superchunk program free of host callbacks (with a seeded
``debug_callback`` as the positive control); the sanitizer enforces the
dispatch contract ``<= ceil(C/K) + 2`` with zero implicit transfers at
K = 8 and zero recompilations on a warm replay resume.
"""

import dataclasses
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (DispatchContract, SanitizerError, dispatch_bound,
                            dispatch_contract, estimate_dispatches,
                            lint_source, sanitized)
from repro.analysis.astlint import load_baseline, partition
from repro.analysis.jaxprlint import audit_callable, audit_engine
from repro.core import RSMConfig, SimConfig
from repro.core.simulator import build_spec, run_simulation

BFT1 = RSMConfig.bft(1)


def _one(src: str, rule: str):
    """Lint a fixture and assert exactly one finding of ``rule``."""
    findings = lint_source(textwrap.dedent(src), path="fixture.py")
    assert [f.rule for f in findings] == [rule], findings
    return findings[0]


def _none(src: str):
    findings = lint_source(textwrap.dedent(src), path="fixture.py")
    assert findings == [], findings


# --- astlint: one fixture per rule, positive + suppressed ----------------

SEEDED_ITEM_IN_SCAN = """
    import jax

    def _build(spec):
        def step(carry, x):
            v = carry + x
            bad = v.item(){SUPPRESS}
            return carry, bad

        def run(xs):
            return jax.lax.scan(step, 0, xs)

        return run
"""


def test_rule_host_sync_item_in_scan_body():
    """The acceptance seed: a ``.item()`` inside a scan body is found,
    named, and carries the fix-it hint."""
    f = _one(SEEDED_ITEM_IN_SCAN.format(SUPPRESS=""), "host-sync")
    assert f.symbol == "_build.step"
    assert ".item()" in f.message
    assert "drain" in f.hint
    assert f.fingerprint() == "host-sync::fixture.py::_build.step"


def test_rule_host_sync_suppressed_inline():
    _none(SEEDED_ITEM_IN_SCAN.format(
        SUPPRESS="  # analysis: ignore[host-sync]"))


def test_rule_host_sync_np_asarray_and_device_get():
    f = _one("""
        import jax, numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x) + 1
    """, "host-sync")
    assert "np.asarray" in f.message
    f = _one("""
        import jax

        @jax.jit
        def f(x):
            return jax.device_get(x)
    """, "host-sync")
    assert "device_get" in f.message


def test_rule_tracer_branch():
    src = """
        import jax

        @jax.jit
        def f(x):
            y = x + 1
            {LINE}
                y = y * 2
            return y
    """
    f = _one(src.format(LINE="if y > 0:"), "tracer-branch")
    assert "lax.cond" in f.message
    _none(src.format(LINE="if y > 0:  # analysis: ignore[tracer-branch]"))
    # static config dispatch (string compare) is not flagged
    _none("""
        import jax

        @jax.jit
        def f(x, kind):
            if kind == "rwkv":
                return x * 2
            return x
    """)
    # jit static_argnames are static at trace time
    _none("""
        import jax

        @jax.jit(static_argnames=("n",))
        def f(x, n):
            if n > 4:
                return x * 2
            return x
    """)


def test_rule_import_time_jnp():
    f = _one("""
        import jax.numpy as jnp

        BIG = jnp.int32(2 ** 30)
    """, "import-time-jnp")
    assert "import time" in f.message
    _none("""
        import jax.numpy as jnp

        BIG = 2 ** 30

        def f():
            return jnp.int32(BIG)
    """)


def test_rule_missing_donate():
    src = """
        import jax

        def _build(spec):
            def step(carry, x):
                return carry + x, x

            def run(state, xs):
                return jax.lax.scan(step, state, xs)

            return run

        def compiled(spec):
            return jax.jit(_build(spec){DONATE})
    """
    f = _one(src.format(DONATE=""), "missing-donate")
    assert "donate_argnums" in f.message
    assert f.symbol.startswith("compiled->")
    _none(src.format(DONATE=", donate_argnums=(0,)"))


def test_rule_pytree_fields():
    f = _one("""
        import dataclasses
        import jax.numpy as jnp

        @dataclasses.dataclass(frozen=True)
        class Spec:
            steps: int
            masks: jnp.ndarray
    """, "pytree-fields")
    assert "Spec.masks" in f.symbol
    _none("""
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class Spec:
            steps: int
            masks: tuple
    """)


def test_repo_tree_is_clean_modulo_baseline():
    """The gate invariant CI enforces: zero unbaselined findings on the
    tree, and no stale baseline entries."""
    from repro.analysis.astlint import lint_tree
    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    baseline_path = os.path.join(os.path.dirname(__file__), "..",
                                 "ANALYSIS_BASELINE.txt")
    findings = lint_tree(os.path.relpath(root))
    baseline = load_baseline(os.path.relpath(baseline_path))
    new, old = partition(findings, baseline)
    assert new == [], [f.render() for f in new]
    live = {f.fingerprint() for f in findings}
    assert baseline <= live, f"stale baseline entries: {baseline - live}"


# --- jaxprlint -----------------------------------------------------------

def test_audit_engine_superchunk_free_of_host_callbacks():
    """The engine's actual compiled programs — dense, chunk, final
    chunk, K=8 superchunk — contain zero host callbacks, zero dtype
    widenings, at the jaxpr AND lowered-module level."""
    report = audit_engine(m=48, window_slots=16, chunk_steps=4,
                          superchunk=4)
    assert report["ok"], report["violations"]
    names = {p["name"] for p in report["programs"]}
    assert {"dense", "chunk", "chunk_final", "superchunk"} <= names
    sc = next(p for p in report["programs"] if p["name"] == "superchunk")
    assert sc["host_callbacks"] == [] or sc["host_callbacks"] == ()
    assert sc["lowered_callback_calls"] == 0
    assert "scan" in sc["primitives"]


def test_audit_callable_detects_seeded_callback():
    """Positive control: a debug_callback smuggled into a scan body is
    reported (so the zero-callback certification is falsifiable)."""
    def leaky(xs):
        def step(c, x):
            jax.debug.callback(lambda v: None, x)
            return c + x, x
        return jax.lax.scan(step, jnp.int32(0), xs)

    audit = audit_callable(leaky, (jnp.arange(4, dtype=jnp.int32),),
                           "leaky")
    assert not audit.ok
    assert "debug_callback" in audit.host_callbacks
    assert any("debug_callback" in v for v in audit.violations())


def test_audit_callable_detects_widening():
    def widens(x):
        return x.astype(jnp.float64) if jax.config.jax_enable_x64 \
            else x.astype(jnp.int32) + jnp.int32(1)

    # x64 disabled (repo default): int32 math stays clean
    clean = audit_callable(widens, (jnp.arange(3, dtype=jnp.int32),),
                           "clean")
    assert clean.ok


@pytest.mark.parametrize("host", [True, False])
def test_audit_callable_detects_device_constant(host):
    """A jax.Array closed over by a program (not a numpy constant) is
    reported, in a scan body too: lowering fetches it to the host."""
    table = np.arange(8, dtype=np.int32)
    if not host:
        table = jnp.asarray(table)

    def lookup(xs):
        return jax.lax.scan(
            lambda c, x: (c + jnp.asarray(table)[x], x), 0, xs)

    audit = audit_callable(lookup, (jnp.arange(4, dtype=jnp.int32),),
                           "lookup")
    assert audit.ok == host
    assert audit.device_constants == (() if host else ((8,),))


def test_estimate_matches_engine_span_arithmetic():
    # 42 full chunks at K=8: 5 spans of 8 + tail — measured 7 on the
    # real engine (test below keeps them honest against each other)
    assert estimate_dispatches(168, 4, 8) == 7
    assert estimate_dispatches(168, 4, 1) == 42
    assert estimate_dispatches(40, 4, 8) == 3
    assert estimate_dispatches(124, 32, 8) == 2
    for steps, c, k in [(168, 4, 8), (40, 4, 2), (200, 8, 4)]:
        n_chunks = -(-steps // c)
        assert estimate_dispatches(steps, c, k) <= dispatch_bound(
            steps, c, k), (steps, c, k)
        assert estimate_dispatches(steps, c, 1) == n_chunks


# --- sanitizer -----------------------------------------------------------

def _spec(k: int, **over):
    kw = dict(n_msgs=128, steps=128 // 4 + 40, window=1, phi=6,
              window_slots=64, chunk_steps=4, superchunk=k,
              debug_checks=True)
    kw.update(over)
    return build_spec(BFT1, BFT1, SimConfig(**kw))


def test_sanitizer_dispatch_contract_k8():
    """The acceptance contract: a K = 8 run fits ceil(C/K) + 2
    dispatches with zero implicit device->host transfers, measured
    under SimConfig.debug_checks (engine guard nested inside)."""
    spec = _spec(8)
    run_simulation(spec)                        # warm
    with sanitized(dispatch_contract(spec, warm=True)) as rep:
        run_simulation(spec)
    n_chunks = -(-spec.steps // spec.chunk_steps)
    assert rep.dispatches <= -(-n_chunks // 8) + 2
    assert rep.transfers == ()
    assert rep.recompiles == 0
    assert rep.host_syncs <= rep.dispatches + 2


def test_sanitizer_warm_replay_resume_zero_recompiles():
    """Replay resume under the sanitizer: zero fresh tracings, zero
    implicit transfers — the recorded parent compiled every program the
    resumed tail reuses."""
    from repro.replay import record_simulation, replay

    spec = _spec(8, n_msgs=96, steps=120, window_slots=24, chunk_steps=8)
    r0, trace = record_simulation(spec, every=2)
    mid = trace.boundaries()[len(trace.boundaries()) // 2]
    contract = DispatchContract(max_recompiles=0, max_transfers=0,
                                sync_slack=2, label="replay resume")
    with sanitized(contract) as rep:
        replayed = replay(trace, int(mid))[0]
    assert rep.recompiles == 0
    assert rep.transfers == ()
    assert np.array_equal(replayed.deliver_time, r0.deliver_time)


def test_sanitizer_flags_implicit_transfer():
    x = jnp.arange(8)
    with pytest.raises(SanitizerError, match="implicit device->host"):
        with sanitized(DispatchContract(max_transfers=0)):
            np.asarray(x)
    # the sanctioned route stays silent
    with sanitized(DispatchContract(max_transfers=0)) as rep:
        jax.device_get(x)
    assert rep.transfers == ()
    # host->host numpy conversions are not transfers
    with sanitized(DispatchContract(max_transfers=0)) as rep:
        np.asarray([1, 2, 3])
    assert rep.transfers == ()


def test_sanitizer_contract_violation_message_names_ceiling():
    spec = _spec(1, n_msgs=32, steps=24, window_slots=32)
    run_simulation(spec)
    tight = DispatchContract(max_dispatches=1, label="tight")
    with pytest.raises(SanitizerError, match="dispatches > contract 1"):
        with sanitized(tight):
            run_simulation(spec)


def test_engine_guard_behind_debug_checks():
    """debug_checks wires the engine guard: results identical, and the
    guard composes with an outer sanitized() (both see the counters)."""
    spec = _spec(4)
    off = dataclasses.replace(spec, debug_checks=False)
    a, b = run_simulation(spec), run_simulation(off)
    assert np.array_equal(a.deliver_time, b.deliver_time)
    with sanitized(dispatch_contract(spec, warm=True)) as rep:
        run_simulation(spec)
    assert rep.dispatches > 0 and rep.transfers == ()


def test_engine_guard_catches_seeded_transfer():
    from repro.analysis.sanitizer import engine_guard
    x = jnp.arange(4)
    with pytest.raises(SanitizerError, match="implicit device->host"):
        with engine_guard():
            np.asarray(x)


def test_dispatch_bound_shapes():
    assert dispatch_bound(168, 4, 8) == -(-42 // 8) + 2
    assert dispatch_bound(168, 4, 1) == 44
    assert dispatch_bound(40, 0, 8) == 3        # dense: one dispatch
    assert dispatch_bound(1, 4, 8) == 3


# --- CLI gate ------------------------------------------------------------

def test_cli_check_passes_on_tree():
    """`python -m repro.analysis --check --skip-engine` exits 0 on the
    repo (the engine passes run in their own tests above)."""
    from repro.analysis.__main__ import main
    root = os.path.relpath(
        os.path.join(os.path.dirname(__file__), "..", "src", "repro"))
    base = os.path.relpath(
        os.path.join(os.path.dirname(__file__), "..",
                     "ANALYSIS_BASELINE.txt"))
    assert main(["--check", "--skip-engine", "--root", root,
                 "--baseline", base]) == 0


def test_cli_check_fails_on_seeded_violation(tmp_path, capsys):
    """The documented gate failure: an unbaselined `.item()`-in-scan
    violation seeded into a tree makes `--check` exit 1 and print the
    finding with its hint."""
    bad = tmp_path / "seeded.py"
    bad.write_text(textwrap.dedent(SEEDED_ITEM_IN_SCAN.format(SUPPRESS="")))
    from repro.analysis.__main__ import main
    rc = main(["--check", "--skip-engine", "--root", str(tmp_path),
               "--baseline", str(tmp_path / "NO_BASELINE.txt")])
    assert rc == 1
    out = capsys.readouterr().out
    assert "[host-sync]" in out and "hint:" in out
    # baselining the fingerprint turns the same tree green
    fp = f"host-sync::{os.path.relpath(bad)}::_build.step"
    (tmp_path / "BASE.txt").write_text(fp + "\n")
    rc = main(["--check", "--skip-engine", "--root", str(tmp_path),
               "--baseline", str(tmp_path / "BASE.txt")])
    assert rc == 0
