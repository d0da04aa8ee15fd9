"""Runtime dispatch/transfer sanitizer for the windowed engine.

The third analysis pass runs *alongside* real executions. Where
``astlint`` checks source and ``jaxprlint`` checks staged programs,
the sanitizer checks what actually happened: how many device dispatches
the engine issued, how often the host blocked on device results,
whether any device array was implicitly materialized on the host, and
whether a warm path re-traced a program it should have reused.

The declarative contract (ISSUE 7 / ROADMAP "kill the remaining host
round-trips"):

    a windowed run of C chunks at fusion K issues
        <= ceil(C / K) + 2 dispatches,
    with 0 implicit device->host transfers and
         0 recompilations on a warm (replay resume) path.

Usage::

    from repro.analysis import dispatch_contract, sanitized

    with sanitized(dispatch_contract(spec)) as report:
        run_simulation(spec)
    # raises SanitizerError on violation; `report` holds the deltas

Implicit-transfer detection: ``jax.transfer_guard`` is installed for
backends where it bites, but the CPU client shares buffers with the
host, so device->host guards never fire there. The sanitizer therefore
also interposes on ``np.asarray`` / ``np.array`` (the only routes
through which a ``jax.Array`` silently becomes host memory in this
codebase) and on ``jax.device_get`` (the *sanctioned* route, which
marks its dynamic extent as explicit). A conversion of a committed
``jax.Array`` outside an explicit fetch is recorded as an implicit
transfer. Interposition is refcounted and thread-aware, so nested
sanitizers (e.g. a test's ``sanitized`` around the engine's own
``debug_checks`` guard) each see every event.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Iterator, List, Optional, Tuple

import jax
import numpy as np

__all__ = ["DispatchContract", "SanitizerError", "SanitizerReport",
           "dispatch_bound", "dispatch_contract", "sanitized",
           "engine_guard"]


class SanitizerError(RuntimeError):
    """A sanitized execution violated its dispatch/transfer contract."""


def dispatch_bound(steps: int, chunk_steps: int, k: int) -> int:
    """The contract ceiling ``ceil(C/K) + 2`` for a windowed run.

    C = ceil(steps / chunk_steps) chunks; fusion K collapses full-rate
    interior chunks ~K per dispatch; the +2 covers the unfused final
    chunk and one span truncated at the stream tail. Dense runs
    (``chunk_steps <= 0``) are a single dispatch, same slack.
    """
    if chunk_steps is None or chunk_steps <= 0:
        return 3
    n_chunks = -(-max(steps, 1) // chunk_steps)
    return -(-n_chunks // max(k or 1, 1)) + 2


@dataclasses.dataclass(frozen=True)
class DispatchContract:
    """Ceilings a sanitized execution must respect.

    ``None`` disables the corresponding check. ``sync_slack`` bounds
    host syncs relative to *observed* dispatches (each dispatch may
    drain once; +slack for the final flush and checkpoint reads).
    """

    max_dispatches: Optional[int] = None
    max_recompiles: Optional[int] = None     # 0 == warm-path contract
    max_transfers: Optional[int] = 0
    sync_slack: Optional[int] = 2
    label: str = ""


def dispatch_contract(spec: Any, *, warm: bool = False,
                      label: str = "") -> DispatchContract:
    """Contract for one engine run of ``spec`` (SimSpec or SimConfig —
    anything with ``steps`` / ``chunk_steps`` / ``superchunk``)."""
    bound = dispatch_bound(int(getattr(spec, "steps", 0) or 0),
                           int(getattr(spec, "chunk_steps", 0) or 0),
                           int(getattr(spec, "superchunk", 1) or 1))
    return DispatchContract(
        max_dispatches=bound,
        max_recompiles=0 if warm else None,
        max_transfers=0, sync_slack=2,
        label=label or f"dispatch<=ceil(C/K)+2={bound}")


@dataclasses.dataclass
class SanitizerReport:
    """Deltas observed inside one ``sanitized`` region."""

    contract: Optional[DispatchContract] = None
    dispatches: int = 0
    host_syncs: int = 0
    recompiles: int = 0
    transfers: Tuple[str, ...] = ()
    closed: bool = False

    def violations(self) -> List[str]:
        c = self.contract
        out = []
        if c is None:
            return out
        if (c.max_dispatches is not None
                and self.dispatches > c.max_dispatches):
            out.append(f"{self.dispatches} dispatches > contract "
                       f"{c.max_dispatches} ({c.label})")
        if (c.max_recompiles is not None
                and self.recompiles > c.max_recompiles):
            out.append(f"{self.recompiles} recompilations > contract "
                       f"{c.max_recompiles} (warm path must reuse "
                       f"compiled chunk programs)")
        if (c.max_transfers is not None
                and len(self.transfers) > c.max_transfers):
            out.append(f"{len(self.transfers)} implicit device->host "
                       f"transfers (want <= {c.max_transfers}): "
                       + "; ".join(self.transfers[:4]))
        if (c.sync_slack is not None
                and self.host_syncs > self.dispatches + c.sync_slack):
            out.append(f"{self.host_syncs} host syncs > dispatches "
                       f"({self.dispatches}) + {c.sync_slack}")
        return out

    @property
    def ok(self) -> bool:
        return not self.violations()

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["transfers"] = list(self.transfers)
        d["violations"] = self.violations()
        d["ok"] = self.ok
        return d


# ---------------------------------------------------------------------------
# implicit-transfer interposition (refcounted, multi-collector)

_LOCK = threading.Lock()
_INSTALLS = 0
_COLLECTORS: List[List[str]] = []
_ORIG_ASARRAY = None
_ORIG_ARRAY = None
_ORIG_DEVICE_GET = None
_TLS = threading.local()


def _explicit_depth() -> int:
    return getattr(_TLS, "depth", 0)


def _is_committed_device_array(x: Any) -> bool:
    # Tracers are jax.Array too; converting one is a *trace* error the
    # AST linter owns, not a runtime transfer.
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


def _record(kind: str, x: Any) -> None:
    if _explicit_depth() > 0:
        return
    desc = (f"{kind} on jax.Array shape={getattr(x, 'shape', '?')} "
            f"dtype={getattr(x, 'dtype', '?')} (use jax.device_get)")
    with _LOCK:
        for sink in _COLLECTORS:
            sink.append(desc)


def _install() -> List[str]:
    """Register a collector; patch numpy/jax entry points on first use."""
    global _INSTALLS, _ORIG_ASARRAY, _ORIG_ARRAY, _ORIG_DEVICE_GET
    sink: List[str] = []
    with _LOCK:
        _COLLECTORS.append(sink)
        _INSTALLS += 1
        if _INSTALLS > 1:
            return sink
        _ORIG_ASARRAY = np.asarray
        _ORIG_ARRAY = np.array
        _ORIG_DEVICE_GET = jax.device_get

    def asarray(a, *args, **kwargs):
        if _is_committed_device_array(a):
            _record("np.asarray", a)
        return _ORIG_ASARRAY(a, *args, **kwargs)

    def array(a, *args, **kwargs):
        if _is_committed_device_array(a):
            _record("np.array", a)
        return _ORIG_ARRAY(a, *args, **kwargs)

    def device_get(tree):
        _TLS.depth = _explicit_depth() + 1
        try:
            return _ORIG_DEVICE_GET(tree)
        finally:
            _TLS.depth -= 1

    np.asarray = asarray
    np.array = array
    jax.device_get = device_get
    return sink


def _uninstall(sink: List[str]) -> None:
    global _INSTALLS
    with _LOCK:
        _COLLECTORS.remove(sink)
        _INSTALLS -= 1
        if _INSTALLS == 0:
            np.asarray = _ORIG_ASARRAY
            np.array = _ORIG_ARRAY
            jax.device_get = _ORIG_DEVICE_GET


def _counters():
    # lazy: the simulator imports numpy/jax heavily; importing it here
    # (not at module import) keeps `repro.analysis` cheap to load and
    # avoids a circular import from the engine's own debug_checks guard.
    from ..core import simulator as sim
    return (sim.chunk_dispatch_count(), sim.host_sync_count(),
            sim.chunk_trace_count())


def _device_to_host_guard():
    """JAX's own device->host transfer guard, refusing implicit
    transfers wherever the backend enforces it (not the CPU client,
    whose buffers the host shares)."""
    return jax.transfer_guard_device_to_host(
        "disallow" if jax.default_backend() != "cpu" else "allow")


@contextlib.contextmanager
def sanitized(contract: Optional[DispatchContract] = None, *,
              check: bool = True) -> Iterator[SanitizerReport]:
    """Run the body under the dispatch/transfer sanitizer.

    Yields a :class:`SanitizerReport` whose fields are filled in when
    the block exits; with ``check`` (default) a violated contract
    raises :class:`SanitizerError`. ``transfer_guard`` is engaged for
    backends that enforce it; the numpy interposition covers the CPU
    client, where XLA buffers are host-shared and the guard is inert.
    """
    report = SanitizerReport(contract=contract)
    d0, s0, t0 = _counters()
    sink = _install()
    try:
        with _device_to_host_guard():
            yield report
    finally:
        _uninstall(sink)
        d1, s1, t1 = _counters()
        report.dispatches = d1 - d0
        report.host_syncs = s1 - s0
        report.recompiles = t1 - t0
        report.transfers = tuple(sink)
        report.closed = True
    if check:
        problems = report.violations()
        if problems:
            raise SanitizerError(
                "sanitizer contract violated:\n  - "
                + "\n  - ".join(problems))


@contextlib.contextmanager
def engine_guard() -> Iterator[None]:
    """The engine's own ``debug_checks`` hook: transfer checking only.

    Wrapped around ``_run_windowed_batch`` when
    ``SimConfig.debug_checks`` is set — any implicit device->host
    materialization inside the drain/checkpoint path raises, with no
    dispatch ceiling (callers compose their own :func:`sanitized` for
    that). Off the CPU, JAX's transfer guard refuses such a transfer
    on the spot; the numpy interposition reports it when the run ends.
    """
    sink = _install()
    try:
        with _device_to_host_guard():
            yield
    finally:
        _uninstall(sink)
    if sink:
        raise SanitizerError(
            "implicit device->host transfer inside the windowed "
            "engine:\n  - " + "\n  - ".join(sink[:8]))
