"""Device time by protocol phase and idle time by program span, both
read from the traced session's profile.

The program names each phase of its protocol step with a
``jax.named_scope`` (``c3b_broadcast``, ``c3b_quorum``,
``c3b_retransmit``, ...), which XLA keeps in each operation's metadata,
and makes each of its host spans (``SpanTracer``) a profiler annotation
of the same name. This module reads the ``.xplane.pb`` the harness
writes, with every event's stats, and reduces the traced session to:

- each device operation's self time (``devtrace``'s rule: a loop less
  its body), summed by the innermost ``c3b_*`` scope in its metadata;
- each stretch of device idle time, summed by the innermost program
  span on the host's main thread that covers it, on the trace's own
  clock.

A program without those scopes or annotations gives nothing to read:
:func:`reduce_events` then leaves the matching part empty, and the
metrics that read it report nothing. :func:`reduce_events` works on
plain events, so ``bench/tests/test_scopes.py`` checks it on a small
recorded slice (``bench/testdata/scope_slice.json``).

    python bench/scopes.py [trace_dir]

prints the reduction of a trace as JSON (default: the harness's
``.bench_out/trace``).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):           # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import devtrace  # noqa: E402

SCOPE = re.compile(r"c3b_[a-z]+")
# the program's host spans (``repro.obs.tracer``); ``run`` is the whole
# engine call, so idle time it alone covers is host work nothing names
PROGRAM_SPANS = ("run", "prologue", "plan", "dispatch", "drain_wait",
                 "unpack", "sink_chunk", "plan_floors", "checkpoint",
                 "window_growth", "dense_migration", "final_flush")
UNNAMED = ("run", "")                   # "": no program span at all


@dataclasses.dataclass(frozen=True)
class StatEvent(devtrace.Event):
    """A trace event with its stats, as ``(name, value)`` pairs."""

    stats: Tuple[Tuple[str, Any], ...] = ()


@dataclasses.dataclass
class Scopes:
    window_s: float                     # the traced session, trace clock
    busy_s: float                       # union of device op intervals
    scope_s: Dict[str, float]           # device self time; "" unscoped
    idle_s: Dict[str, float]            # idle by innermost span; "" none

    @property
    def scoped(self) -> bool:
        return any(k for k in self.scope_s)

    @property
    def spanned(self) -> bool:
        return any(k for k in self.idle_s)

    @property
    def unscoped_share(self) -> float:
        total = sum(self.scope_s.values())
        return self.scope_s.get("", 0.0) / total if total else 0.0

    @property
    def idle_unexplained_s(self) -> float:
        return sum(self.idle_s.get(k, 0.0) for k in UNNAMED)

    def to_dict(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "scope_s": self.scope_s, "idle_s": self.idle_s,
                "unscoped_share": self.unscoped_share,
                "idle_unexplained_s": self.idle_unexplained_s}


def _stat_value(v: Any) -> Any:
    return v if isinstance(v, (int, float, str)) else str(v)


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview of the bytes for anything else."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _ints(v) -> List[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _string(buf, field: int) -> str:
    return next((bytes(v).decode() for f, v in _fields(buf)
                 if f == field), "")


def _found(op_name: str) -> str:
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


def hlo_scopes(proto) -> Dict[str, str]:
    """The ``c3b_*`` scope of every instruction of a serialized
    ``HloProto``, by name.

    An instruction's own ``op_name`` names it where XLA kept it. A
    fusion without one takes the scope most of its fused instructions
    carry; any other instruction without one (XLA's rewrites make some:
    the long prefix scans become two-level ``reduce-window`` pairs) the
    scope of its nearest consumer in its computation that has one, as a
    result is charged to the phase that uses it. Fields: ``HloProto``
    module (1); ``HloModuleProto`` computations (3);
    ``HloComputationProto`` instructions (2), id (5);
    ``HloInstructionProto`` name (1), opcode (2), metadata (7: op_name
    2), id (35), operand ids (36), called computation ids (38)."""
    module = next((v for f, v in _fields(proto) if f == 1), b"")
    comps: Dict[int, List[dict]] = {}
    for f, comp in _fields(module):
        if f != 3:
            continue
        cid, instrs = 0, []
        for g, v in _fields(comp):
            if g == 5:
                cid = v
            elif g == 2:
                ins = {"name": "", "opcode": "", "own": "", "id": 0,
                       "operands": [], "called": []}
                for h, x in _fields(v):
                    if h == 1:
                        ins["name"] = bytes(x).decode()
                    elif h == 2:
                        ins["opcode"] = bytes(x).decode()
                    elif h == 7:
                        ins["own"] = _found(_string(x, 2))
                    elif h == 35:
                        ins["id"] = x
                    elif h == 36:
                        ins["operands"] += _ints(x)
                    elif h == 38:
                        ins["called"] += _ints(x)
                instrs.append(ins)
        comps[cid] = instrs

    fused_of: Dict[int, str] = {}

    def fused(cid: int) -> str:
        """The scope most instructions of computation ``cid`` (and of
        the fusions inside it) carry."""
        if cid not in fused_of:
            fused_of[cid] = ""
            count: Dict[str, int] = defaultdict(int)
            for ins in comps.get(cid, ()):
                sc = named(ins)
                if sc:
                    count[sc] += 1
            if count:
                fused_of[cid] = max(sorted(count), key=count.get)
        return fused_of[cid]

    def named(ins: dict) -> str:
        if ins["own"] or ins["opcode"] != "fusion":
            return ins["own"]
        return next((sc for sc in map(fused, ins["called"]) if sc), "")

    out: Dict[str, str] = {}
    for instrs in comps.values():
        users: Dict[int, List[dict]] = defaultdict(list)
        for ins in instrs:
            for o in ins["operands"]:
                users[o].append(ins)
        for ins in instrs:
            sc = named(ins)
            front, seen = [ins], {ins["id"]}
            while not sc and front:
                nxt = []
                for f in front:
                    for u in users[f["id"]]:
                        if u["id"] not in seen:
                            seen.add(u["id"])
                            sc = sc or named(u)
                            nxt.append(u)
                front = nxt
            out[ins["name"]] = sc
    return out


def _program_id(module_name: str) -> Optional[int]:
    """``jit_superchunk(1690...)`` -> 1690..."""
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def op_metadata(raw: bytes):
    """What a serialized ``XSpace`` says of its device operations: each
    operation's metadata stats by ``(program id, event name)``, and each
    program's instruction scopes (:func:`hlo_scopes`) by program id. The
    profiler keeps an operation's HLO metadata there (``tf_op`` holds
    its ``op_name``), not on its events, and the JAX reader does not
    show it, so the fields needed are decoded here: ``XSpace`` planes
    (1); ``XPlane`` name (2), event metadata (4) and stat metadata (5),
    both maps (key 1, value 2); ``XEventMetadata`` name (2), display
    name (4), stats (5); ``XStat`` metadata id (1), integer (3, 4),
    string (5), bytes (6), reference to a stat metadata's name (7). The
    ``/host:metadata`` plane holds each program's ``HloProto``."""
    ops: Dict[Tuple[int, str], Dict[str, Any]] = {}
    programs: Dict[int, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                md = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not (name.startswith("/device:") or name == "/host:metadata"):
            continue
        for entry in events:
            ev_name, stats = "", {}
            for f, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 4:
                    stats["display_name"] = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1, 0), "")
                    if 3 in st or 4 in st:
                        stats[key] = st.get(3, st.get(4))
                    elif 5 in st:
                        stats[key] = bytes(st[5]).decode()
                    elif 6 in st:
                        stats[key] = st[6]
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
            if name == "/host:metadata":
                pid = _program_id(ev_name)
                if pid is not None and "Hlo Proto" in stats:
                    programs[pid] = hlo_scopes(stats["Hlo Proto"])
            elif "program_id" in stats:
                ops[(int(stats["program_id"]), ev_name)] = {
                    k: v for k, v in stats.items()
                    if isinstance(v, (int, str))}
    return ops, programs


def read_events(trace_dir: str) -> List[StatEvent]:
    """Every event of the trace under ``trace_dir``, with its stats. A
    device operation's also carry its program's id, its metadata's
    ``tf_op`` and the scope :func:`hlo_scopes` gives it (``hlo_scope``),
    its program found by the ``XLA Modules`` event it ran in."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[0], "rb") as f:
        raw = f.read()
    ops, programs = op_metadata(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    out = []
    for p in data.planes:
        runs = sorted((float(ev.start_ns), float(ev.end_ns),
                       _program_id(ev.name))
                      for ln in p.lines if ln.name == "XLA Modules"
                      for ev in ln.events)
        starts = [r[0] for r in runs]
        for ln in p.lines:
            for ev in ln.events:
                stats = tuple((k, _stat_value(v)) for k, v in ev.stats)
                if ln.name == "XLA Ops" and runs:
                    i = bisect.bisect_right(starts, ev.start_ns) - 1
                    pid = runs[i][2] if i >= 0 else None
                    meta = ops.get((pid, ev.name), {})
                    scope = programs.get(pid, {}).get(
                        meta.get("display_name", ""))
                    stats += (("program_id", pid),
                              ("tf_op", meta.get("tf_op", "")))
                    if scope is not None:
                        stats += (("hlo_scope", scope),)
                out.append(StatEvent(p.name, ln.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns), stats))
    return out


def load_events(path: str) -> List[StatEvent]:
    """Events kept as JSON rows ``[plane, line, name, start_ns, dur_ns,
    {stat: value}]``."""
    with open(path) as f:
        return [StatEvent(*row[:5], stats=tuple(row[5].items()))
                for row in json.load(f)]


def scope_of(stats: Sequence[Tuple[str, Any]]) -> str:
    """The scope of a device operation: its ``hlo_scope`` where its
    program's HLO names one, else the innermost ``c3b_*`` scope its
    ``tf_op`` names (``jit(f)/vmap(c3b_quorum)/reduce`` is
    ``c3b_quorum``; the profiler gives some fusions the ``op_name`` of
    an instruction inside them), else ""."""
    by = dict(stats)
    return by.get("hlo_scope") or _found(str(by.get("tf_op", "")))


def _program_spans(events: Sequence[StatEvent], mark: StatEvent
                   ) -> List[Tuple[str, float, float]]:
    """The program's span annotations on the session's own thread."""
    return [(e.name, e.start_ns, e.start_ns + e.dur_ns) for e in events
            if e.plane == mark.plane and e.line == mark.line
            and e.name in PROGRAM_SPANS]


def _innermost(spans: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[float, float, str]]:
    """Split the time the spans cover into pieces named by the span
    begun last among those open (the innermost, as they nest)."""
    points = sorted({x for _, s, e in spans for x in (s, e)})
    starts = defaultdict(list)
    ends = defaultdict(list)
    for i, (_, s, e) in enumerate(spans):
        starts[s].append(i)
        ends[e].append(i)
    open_: List[int] = []
    pieces: List[Tuple[float, float, str]] = []
    for a, b in zip(points, points[1:] + [None]):
        for i in ends.get(a, ()):
            open_.remove(i)
        # longer spans first, so the shortest of those begun together
        # is the innermost
        for i in sorted(starts.get(a, ()),
                        key=lambda j: spans[j][1] - spans[j][2]):
            open_.append(i)
        if b is not None and open_:
            pieces.append((a, b, spans[open_[-1]][0]))
    return pieces


def reduce_events(events: Sequence[StatEvent]) -> Optional[Scopes]:
    """Device self time by scope and idle time by program span in the
    traced session; None where it holds no session or no device op."""
    marks = [e for e in events if e.name == devtrace.SESSION]
    ops = devtrace.device_op_events(events)
    if not marks or not ops:
        return None
    mark = marks[0]
    w0, w1 = mark.start_ns, mark.start_ns + mark.dur_ns
    clipped = [(max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
               for e in ops]
    scope_s: Dict[str, float] = defaultdict(float)
    for e, self_ns in zip(ops, devtrace._self_times(clipped)):
        if self_ns > 0:
            scope_s[scope_of(e.stats)] += self_ns / 1e9
    busy = devtrace._union([(s, e) for s, e in clipped if e > s])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]
    idle_s: Dict[str, float] = defaultdict(float)
    pieces = _innermost(_program_spans(events, mark))
    j = 0
    for s, t in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b = max(pieces[k][0], s), min(pieces[k][1], t)
            if b > a:
                idle_s[pieces[k][2]] += (b - a) / 1e9
                covered += b - a
            k += 1
        if t - s > covered:
            idle_s[""] += (t - s - covered) / 1e9
    return Scopes(window_s=(w1 - w0) / 1e9,
                  busy_s=sum(e - s for s, e in busy) / 1e9,
                  scope_s=dict(scope_s), idle_s=dict(idle_s))


def of(ctx) -> Optional[Scopes]:
    """The reduction of the run's traced session (read once a run);
    None in an untraced run."""
    if ctx.trace is None:
        return None
    if "_scopes" not in ctx.__dict__:
        from bench import harness

        try:
            ctx.__dict__["_scopes"] = reduce_events(
                read_events(str(harness.TRACE_DIR)))
        except FileNotFoundError:
            ctx.__dict__["_scopes"] = None
    return ctx.__dict__["_scopes"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        trace_dir = argv[0]
    else:
        from bench import harness

        trace_dir = str(harness.TRACE_DIR)
    red = reduce_events(read_events(trace_dir))
    print(json.dumps(None if red is None else red.to_dict(), indent=1,
                     sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
