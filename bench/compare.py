"""The comparison that decides ``correct``.

Every session of the window is held to the plain reference of its cell
(``bench/reference/<config's reference>.py``): every lane's retired
prefix after every chunk (the GC frontier, and through it the relay's
commit floors), every lane's per-round counts (messages sent, broadcast
copies, resends, acks, cumulative deliveries, slowest quacked prefix),
one sampled lane's outcome for every message (QUACK round at every
sender, delivery round, retries, which receivers hold it), and the
session's live telemetry (every lane's totals and the last live row).
Each comparison is exact: the protocol runs on integers, and its quorum
sums over unit stakes are small integers in any precision that holds
them, so the limit of every number is 0.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.record import OBS_FIELDS, ROUND_FIELDS, SessionRecord

LIMITS = {
    "undelivered": 0,        # offered messages not delivered
    "session_problems": 0,   # the session's own live-vs-device checks
    "window_compiles": 0,    # programs traced inside the window
    "frontier_diff": 0,      # (lane, chunk) retired prefixes that differ
    "round_diff": 0,         # (lane, round, count) entries that differ
    "message_diff": 0,       # sampled-lane messages whose outcome differs
    "telemetry_diff": 0,     # per-lane totals and live fields that differ
}


def _diff(a, b) -> int:
    """Entries of ``a`` that differ from ``b``; a shape mismatch counts
    every entry of the larger."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return int(max(a.size, b.size, 1))
    return int((a != b).sum())


def compare_session(got: SessionRecord, ref: SessionRecord
                    ) -> Dict[str, int]:
    """Mismatch counts of one program session against the reference."""
    out = {"frontier_diff": _diff(got.chunk_ends, ref.chunk_ends)
           + _diff(got.bases, ref.bases)}
    out["round_diff"] = sum(_diff(got.rounds[f], ref.rounds[f])
                            for f in ROUND_FIELDS)
    per_msg = np.zeros(ref.messages["deliver_time"].shape, bool)
    for name, want in ref.messages.items():
        have = got.messages[name]
        if have.shape != want.shape:
            per_msg[:] = True
            continue
        neq = have != want
        per_msg |= neq.any(axis=0) if neq.ndim == 2 else neq
    out["message_diff"] = int(per_msg.sum())
    tele = abs(len(got.obs) - len(ref.obs))
    for g, r in zip(got.obs, ref.obs):
        tele += sum(_diff(g[f], r[f]) for f in OBS_FIELDS)
    tele += sum(int(got.live.get(f) != ref.live.get(f)) for f in ref.live)
    tele += int(got.delivered != ref.delivered)
    tele += int(got.retired != ref.retired)
    out["telemetry_diff"] = tele
    return out


def checks(runs: List, ref_for, offered: int, warmup=None
           ) -> Dict[str, dict]:
    """Every number compared, each beside its limit.

    ``runs`` are the window's sessions and ``warmup`` the set-up's
    session, held to the same reference (its compiles excepted);
    ``ref_for(lane)`` gives the reference record sampled at ``lane``;
    ``offered`` is the number of messages each session offers.
    """
    total = {k: 0 for k in LIMITS}
    for run in ([warmup] if warmup is not None else []) + list(runs):
        total["session_problems"] += len(run.problems)
        if run is not warmup:
            total["window_compiles"] += int(run.counters.get("traces", 0))
        if run.record is None:
            total["undelivered"] += offered
            for k in ("frontier_diff", "round_diff", "message_diff",
                      "telemetry_diff"):
                total[k] += 1
            continue
        total["undelivered"] += max(offered - run.record.delivered, 0)
        for k, v in compare_session(run.record, ref_for(run.lane)).items():
            total[k] += v
    return {k: {"value": total[k], "limit": LIMITS[k]} for k in LIMITS}


def passed(numbers: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
