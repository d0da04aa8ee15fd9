"""Jaxpr/HLO-level auditor for the engine's actual compiled programs.

``astlint`` reasons about source text; this pass reasons about what JAX
will really stage. It traces the engine's dense, chunk, final-chunk and
superchunk programs exactly as the windowed loop builds them (same
constructors, same argument trees, tiny shapes) and checks, on the
jaxpr and on the lowered module:

* **host callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` equations anywhere inside a fused span. One of
  these inside the superchunk scan serializes the whole span on the
  host and silently destroys the K× dispatch reduction (ROADMAP: the
  saturated-pipeline claim is only as strong as the dispatch path is
  clean).
* **dtype widenings** — ``convert_element_type`` to int64 / float64 /
  complex128. The engine is int32/bool/float32 end to end; an x64
  widening doubles the scan-state footprint and recompiles on
  machines with ``jax_enable_x64`` set.
* **donation** — per-argument input bytes, and whether the scan-state
  argument is donated on backends where XLA implements aliasing (the
  CPU client ignores donation, so there it is reported as info, not a
  violation).
* **device constants** — a ``jax.Array`` closed over by the program.
  Lowering embeds it as a literal, which fetches it back to the host:
  a hidden device->host transfer per compile that the ``debug_checks``
  transfer guard refuses on a TPU. Host constants belong in numpy.
* **dispatch estimates** — the exact number of device dispatches the
  host loop will issue for a (steps, chunk_steps, K) plan, computed by
  replicating the loop's span arithmetic; the sanitizer's runtime
  contract (``ceil(C/K) + 2``) is derived from the same numbers.

``audit_engine`` returns a JSON-ready dict (the ``jaxpr`` section of
``ANALYSIS.json``); the CLI fails ``--check`` when any audited program
is not clean.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

__all__ = ["ProgramAudit", "audit_callable", "audit_engine",
           "estimate_dispatches", "BANNED_PRIMITIVES"]

BANNED_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback")
_WIDE_DTYPES = ("int64", "uint64", "float64", "complex128")


@dataclasses.dataclass
class ProgramAudit:
    """Static audit of one compiled program."""

    name: str
    n_eqns: int
    primitives: Tuple[str, ...]
    host_callbacks: Tuple[str, ...]        # banned primitive instances
    widenings: Tuple[str, ...]             # "int32->int64 (eqn ...)"
    arg_bytes: Tuple[int, ...]             # per top-level argument
    donated_args: Tuple[int, ...]          # argnums declared donated
    undonated_large: Tuple[int, ...]       # large argnums not donated
    donation_enforced: bool                # backend implements aliasing
    lowered_callback_calls: int            # custom_call cross-check
    device_constants: Tuple[Tuple[int, ...], ...] = ()  # closed-over
    notes: str = ""                                     # jax.Array shapes

    @property
    def ok(self) -> bool:
        """Clean = no host callbacks, no widenings, donation honoured
        wherever the backend implements it."""
        return (not self.host_callbacks and not self.widenings
                and self.lowered_callback_calls == 0
                and not self.device_constants
                and (not self.donation_enforced
                     or not self.undonated_large))

    def violations(self) -> List[str]:
        out = []
        for cb in self.host_callbacks:
            out.append(f"{self.name}: host callback '{cb}' inside the "
                       f"compiled program")
        if self.lowered_callback_calls:
            out.append(f"{self.name}: {self.lowered_callback_calls} "
                       f"callback custom-calls in the lowered module")
        for w in self.widenings:
            out.append(f"{self.name}: dtype widening {w}")
        for shape in self.device_constants:
            out.append(f"{self.name}: device-array constant of shape "
                       f"{shape} (lowering fetches it to the host)")
        if self.donation_enforced and self.undonated_large:
            out.append(f"{self.name}: large undonated args "
                       f"{list(self.undonated_large)}")
        return out

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        # primitives can be long; keep the set, drop repetition order
        d["primitives"] = sorted(set(self.primitives))
        return d


def iter_eqns(jaxpr):
    """Yield every equation of ``jaxpr``, descending into sub-jaxprs
    (pjit bodies, scan bodies, cond branches, custom_* calls...)."""
    for _, eqn in iter_scoped_eqns(jaxpr):
        yield eqn


def iter_scoped_eqns(jaxpr, scope: str = ""):
    """:func:`iter_eqns` yielding ``(scope, eqn)``, where ``scope`` is the
    equation's whole name stack (its ``jax.named_scope``s, ``/``-joined):
    a sub-jaxpr's equations carry only the part of the stack inside it,
    so the enclosing equations' part is prefixed."""
    for eqn in jaxpr.eqns:
        here = "/".join(p for p in (scope, str(eqn.source_info.name_stack))
                        if p)
        yield here, eqn
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for item in vals:
                sub = getattr(item, "jaxpr", None)
                if sub is not None:              # ClosedJaxpr
                    yield from iter_scoped_eqns(sub, here)
                elif hasattr(item, "eqns"):      # raw Jaxpr
                    yield from iter_scoped_eqns(item, here)


def _device_constants(closed) -> List[Tuple[int, ...]]:
    """Shapes of the ``jax.Array`` constants of ``closed`` and of every
    closed sub-jaxpr (scan/cond/pjit bodies)."""
    consts = list(closed.consts)
    for eqn in iter_eqns(closed.jaxpr):        # already recursive
        for val in eqn.params.values():
            for item in (val if isinstance(val, (tuple, list)) else (val,)):
                consts += getattr(item, "consts", ())
    return [tuple(c.shape) for c in consts if isinstance(c, jax.Array)]


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "shape"))


def audit_callable(fn, args: Sequence[Any], name: str,
                   donate: Tuple[int, ...] = (),
                   large_bytes: int = 1 << 20,
                   lowered_text: Optional[str] = None) -> ProgramAudit:
    """Trace ``fn(*args)`` and audit the staged program.

    ``donate`` is the donate_argnums the caller compiles with;
    ``lowered_text``, when given, is the lowered module text used for
    the callback custom-call cross-check (pass it for jitted callables;
    omitting it skips the HLO-level check).
    """
    closed = jax.make_jaxpr(fn)(*args)
    prims: List[str] = []
    callbacks: List[str] = []
    widenings: List[str] = []
    for eqn in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        prims.append(prim)
        if prim in BANNED_PRIMITIVES:
            callbacks.append(prim)
        if prim == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if any(new.startswith(w) for w in _WIDE_DTYPES):
                old = str(eqn.invars[0].aval.dtype)
                widenings.append(f"{old}->{new}")

    arg_bytes = tuple(_tree_bytes(a) for a in args)
    undonated = tuple(i for i, b in enumerate(arg_bytes)
                      if b >= large_bytes and i not in donate)
    callback_calls = 0
    if lowered_text is not None:
        callback_calls = lowered_text.count("callback")
    return ProgramAudit(
        name=name, n_eqns=len(prims), primitives=tuple(prims),
        host_callbacks=tuple(callbacks), widenings=tuple(widenings),
        arg_bytes=arg_bytes, donated_args=tuple(donate),
        undonated_large=undonated,
        donation_enforced=jax.default_backend() != "cpu",
        lowered_callback_calls=callback_calls,
        device_constants=tuple(_device_constants(closed)))


def estimate_dispatches(steps: int, chunk_steps: int, k: int) -> int:
    """Device dispatches the windowed host loop issues for this plan.

    Replicates ``_run_windowed_batch``'s span arithmetic exactly
    (fusion capped at K, broken at the final/partial chunk), assuming
    no mandatory host boundary fires mid-run — the clean-pipeline
    number the sanitizer contract is measured against.
    """
    c_full = max(chunk_steps, 1)
    t, n = 0, 0
    while t < steps:
        c = min(c_full, steps - t)
        last = t + c >= steps
        span = 1
        if not last and c == c_full:
            span = max(1, min(max(k, 1), (steps - t - 1) // c_full))
        n += 1
        t += span * c
    return n


def _tiny_spec(m: int = 64, window_slots: int = 16, chunk_steps: int = 4,
               superchunk: int = 8):
    from ..core import RSMConfig, SimConfig
    from ..core.simulator import build_spec
    rsm = RSMConfig.bft(1)
    sim = SimConfig(n_msgs=m, steps=m // 4 + 24, window=1, phi=6,
                    window_slots=window_slots, chunk_steps=chunk_steps,
                    superchunk=superchunk)
    return build_spec(rsm, rsm, sim)


def audit_engine(m: int = 64, window_slots: int = 16,
                 chunk_steps: int = 4, superchunk: int = 8,
                 with_lowered: bool = True) -> Dict[str, Any]:
    """Audit the engine's real programs at a tiny windowed shape.

    Programs audited (the same constructors the host loop calls — the
    audit cannot drift from the implementation):

    * ``dense``          — the full-M runner (``_build_run``);
    * ``chunk``          — one rotating windowed chunk, batched
                           (``_build_chunk`` + vmap). This is ALSO the
                           replay resume/injection program (K = 1) and
                           the chained-topology program (commit floors
                           are traced inputs of the same jaxpr);
    * ``chunk_final``    — the unrotated final chunk;
    * ``superchunk``     — K fused chunk bodies (``lax.scan`` over
                           boundaries), the pipelined hot path;
    * ``chunk_obs`` / ``superchunk_obs`` — the same chunk/superchunk
                           programs with the in-graph metrics fabric on
                           (``collect_metrics=True``, carry =
                           ``(SimState, MetricsCarry)``): the
                           observability layer must satisfy the exact
                           same cleanliness contract as the bare engine
                           (no callbacks, no widenings, donated carry);
    * ``chunk_stream`` / ``superchunk_stream`` — horizon-mode programs
                           staged at a ``repro.stream`` spec (arrival-
                           driven ``orig_step``, load-sized window,
                           metrics carry feeding the live drain sink):
                           the resident streaming service runs these
                           exact programs over unbounded horizons.
    """
    import dataclasses as dc

    import jax.numpy as jnp

    from ..core.simulator import (_build_chunk, _build_run, _donate_state,
                                  _fail_arrays, _init_state, _neutral,
                                  superchunk_program)

    spec = _tiny_spec(m, window_slots, chunk_steps, superchunk)
    nspec = _neutral(spec)
    cspec = dc.replace(nspec, steps=0)
    w, c, k = spec.window_slots, spec.chunk_steps, spec.superchunk

    fails = _fail_arrays(spec)
    bfails = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(
        x, (1,) + jnp.shape(x)), fails)
    state = _init_state(nspec, w)
    bstate = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (1,) + x.shape), state)
    t0 = jnp.int32(0)
    donate = _donate_state()

    audits: List[ProgramAudit] = []

    dense_fn = _build_run(nspec)
    audits.append(audit_callable(
        dense_fn, (fails,), "dense",
        lowered_text=(jax.jit(dense_fn).lower(fails).as_text()
                      if with_lowered else None)))

    for rotate, name in ((True, "chunk"), (False, "chunk_final")):
        fn = jax.vmap(_build_chunk(cspec, w, c, rotate),
                      in_axes=(0, 0, None))
        audits.append(audit_callable(
            fn, (bfails, bstate, t0), name, donate=donate,
            lowered_text=(jax.jit(fn, donate_argnums=donate)
                          .lower(bfails, bstate, t0).as_text()
                          if with_lowered else None)))

    # the superchunk programs, staged through the real cached
    # constructor at the shapes the host loop calls them with
    def audit_superchunk(sc_spec, name):
        sc, sc_args = superchunk_program(sc_spec)
        audits.append(audit_callable(
            sc, sc_args, name, donate=donate,
            lowered_text=(sc.lower(*sc_args).as_text()
                          if with_lowered else None)))

    audit_superchunk(spec, "superchunk")

    # the observability fabric's programs: same constructors with
    # collect_metrics on, scan carry = (SimState, MetricsCarry)
    from ..obs.metrics import init_metrics_carry
    mspec = dc.replace(cspec, collect_metrics=True)
    bmc = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (1,) + jnp.shape(x)),
        init_metrics_carry(w))
    bcarry = (bstate, bmc)
    fn_obs = jax.vmap(_build_chunk(mspec, w, c, True),
                      in_axes=(0, 0, None))
    audits.append(audit_callable(
        fn_obs, (bfails, bcarry, t0), "chunk_obs", donate=donate,
        lowered_text=(jax.jit(fn_obs, donate_argnums=donate)
                      .lower(bfails, bcarry, t0).as_text()
                      if with_lowered else None)))
    audit_superchunk(dc.replace(spec, collect_metrics=True),
                     "superchunk_obs")

    # horizon-mode (streaming-session) programs: the same chunk /
    # superchunk constructors, staged at a *stream* spec — an
    # arrival-process ``orig_step`` schedule, a load-sized window from
    # ``stream_window_slots`` and the metrics carry that feeds the live
    # drain sink. The resident-service hot path must satisfy the exact
    # same cleanliness contract as the batch engine; the import is lazy
    # (repro.stream sits above repro.analysis in the layer order).
    from ..core import RSMConfig, SimConfig
    from ..stream.workload import ArrivalProcess, build_stream_spec
    sspec = build_stream_spec(
        RSMConfig.bft(1), RSMConfig.bft(1),
        SimConfig(window=1, phi=6, window_slots="auto",
                  chunk_steps=chunk_steps, superchunk=superchunk),
        ArrivalProcess(kind="constant", rate=4.0), horizon=m)
    s_cspec = dc.replace(_neutral(sspec), steps=0)
    sw, s_c = sspec.window_slots, sspec.chunk_steps
    sfails = _fail_arrays(sspec)
    sbfails = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(
        x, (1,) + jnp.shape(x)), sfails)
    sbcarry = (
        jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (1,) + x.shape),
            _init_state(s_cspec, sw)),
        jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (1,) + jnp.shape(x)),
            init_metrics_carry(sw)))
    fn_stream = jax.vmap(_build_chunk(s_cspec, sw, s_c, True),
                         in_axes=(0, 0, None))
    audits.append(audit_callable(
        fn_stream, (sbfails, sbcarry, t0), "chunk_stream",
        donate=donate,
        lowered_text=(jax.jit(fn_stream, donate_argnums=donate)
                      .lower(sbfails, sbcarry, t0).as_text()
                      if with_lowered else None)))
    audit_superchunk(sspec, "superchunk_stream")

    n_chunks = -(-spec.steps // c)
    estimates = []
    for kk in sorted({1, 2, k, 8}):
        estimates.append(dict(
            steps=spec.steps, chunk_steps=c, k=kk, n_chunks=n_chunks,
            dispatches=estimate_dispatches(spec.steps, c, kk),
            contract_bound=-(-n_chunks // kk) + 2))

    violations = [v for a in audits for v in a.violations()]
    return {
        "shape": dict(m=spec.m, steps=spec.steps, window_slots=w,
                      chunk_steps=c, superchunk=k,
                      backend=jax.default_backend()),
        "programs": [a.to_dict() for a in audits],
        "program_reuse": {
            "replay_resume": "chunk (K=1, zero-recompilation contract)",
            "topology_chained": "chunk (commit floors are traced inputs)",
        },
        "dispatch_estimates": estimates,
        "violations": violations,
        "ok": not violations,
    }
