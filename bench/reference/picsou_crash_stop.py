"""Plain numpy reference of PICSOU streaming links under crash-stop
failures: unit stakes, each replica either correct or crashed from a
given round onward.

Written from the protocol's description, like ``picsou_failure_free``
(whose quorum sizes, per-message layout and record it reuses), with the
crash rules of the paper's failure model added. It imports nothing of
the system under test. A crash placement gives, per replica of each RSM,
the round from which it is down (-1: never); a replica that crashes at
any round is a faulty one, the others are correct.

The rules, each with the paper's section:

- §2.1 (UpRight model): a crash is an omission failure; a crashed
  replica sends nothing from its crash round on, and nothing sent to it
  lands. Delivery (C3B) needs a *correct* receiver to hold the message,
  so a message held only by receivers that crash at some round is not
  yet delivered.
- §4.1: a crashed sender originates nothing: its scheduled messages are
  dispatched (the attempt is made, and counted as the message's send
  round) but never reach the wire. A crashed receiver lands nothing,
  broadcasts nothing it had queued, and acks nobody: the ack rotation
  skips it and its complaint trackers freeze.
- §4.1: a QUACK needs u_r + 1 receivers' claims; §4.2: a loss needs
  r_r + 1 receivers' repeated complaints. Both count what receivers
  reported while they were up: an ack once received stays known to the
  sender, and nothing more comes from a receiver after its crash.
- §4.2: the a-th retransmission of message k is elected to sender
  ``(k + a) mod n_s`` and sent to receiver ``(orig_recv[k] + a) mod
  n_r``. A crashed elected sender sends nothing; a retransmission to a
  crashed receiver is sent (and counted) but lost. Either way the
  sender's trackers were cleared, so the next declaration, and with it
  the next candidate, waits for fresh repeated complaints.
- §4.3: the highest-quacked prefix rides only what a live sender sends
  and is heard only by a live receiver; a receiver's ack floor is the
  prefix r_s + 1 senders' reports reach.
- §4.3 (GC): at the end of a chunk the link retires the leading messages
  that are dispatched, quacked at every sender, have no broadcast
  queued, and are held (or below its floor) by every receiver that is
  still up in the next round, so still acks.

Departures from the paper, each also the system's model:

- A crashed sender's QUACK bookkeeping keeps running: receivers' acks to
  it in the rotation are recorded as at any sender (and reported as its
  QUACK rounds), a loss quorum at it still counts a declaration and a
  retry, but it never acts on either. The paper's crashed replica keeps
  no state; here that state is only observed, and the GC rule waits for
  it as for any sender's.
- The minimum quacked prefix of a round (telemetry) is taken over
  correct senders only; with none, it reads 2**30.
- Crash rounds are fixed for a run and no replica recovers.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.record import ROUND_FIELDS
# ``as_record`` is this reference's too: the lanes keep the same fields
from bench.reference.picsou_failure_free import (  # noqa: F401
    Link, Protocol, as_record, latency_bucket)

NO_CORRECT_SENDER = 2 ** 30     # telemetry's quacked prefix with none


def _prefixes(ok: np.ndarray) -> np.ndarray:
    """Leading run of True in each row of ``ok``."""
    if ok.shape[1] == 0:
        return np.zeros(ok.shape[0], np.int64)
    return np.where(ok.all(axis=1), ok.shape[1], np.argmin(ok, axis=1))


def _up(crash: np.ndarray, t: int) -> np.ndarray:
    """Replicas not crashed by round ``t``."""
    return (crash < 0) | (t < crash)


class CrashLink(Link):
    """One link's whole state, with crash rounds per replica."""

    def __init__(self, p: Protocol, ostep: np.ndarray,
                 crash_s: np.ndarray, crash_r: np.ndarray):
        super().__init__(p, ostep)
        self.crash_s = np.asarray(crash_s, np.int64)
        self.crash_r = np.asarray(crash_r, np.int64)
        if self.crash_s.shape != (p.n_s,) or self.crash_r.shape != (p.n_r,):
            raise ValueError("a crash placement gives one round per replica")
        self.correct_r = self.crash_r < 0
        self.correct_s = self.crash_s < 0

    def step(self, t: int, floor: int) -> Dict[str, int]:
        p, m = self.p, self.m
        n_s, n_r = p.n_s, p.n_r
        up_s, up_r = _up(self.crash_s, t), _up(self.crash_r, t)
        arrived = int(np.searchsorted(self.ostep, t, side="right"))
        hi_new = max(self.hi, min(floor, arrived))
        a, b = self.lo, min(hi_new + 1, m)
        idx = np.arange(a, b)
        S = slice(a, b)

        # (1) queued broadcasts go out from live receivers and land at
        # every other live receiver
        bq = self.bq[:, S] & up_r[:, None]
        n_bq = bq.sum(axis=0)
        intra = int(n_bq.sum()) * (n_r - 1)
        self.recv[:, S] |= ((n_bq[None, :] - bq) > 0) & up_r[:, None]
        self.bdone[:, S] |= bq

        # (2) loss declaration at every sender, from last round's
        # knowledge; only the elected sender, if up, retransmits
        quacked_prev = self.n_known[:, S] >= p.quack
        declared = ((self.n_rep[:, S] >= p.dup) & ~quacked_prev
                    & (idx < self.hi)[None, :])
        self.retry[:, S] += declared
        retry = self.retry[:, S]
        resend = (declared & ((idx[None, :] + retry) % n_s
                              == np.arange(n_s)[:, None])
                  & up_s[:, None])
        re_target = (self.orecv[S][None, :] + retry) % n_r
        if declared.any():
            self.compl[:, :, S] &= ~declared[:, None, :]
            self.rep[:, :, S] &= ~declared[:, None, :]
            self.n_rep[:, S][declared] = 0

        # (3) original sends from live senders and retransmissions land
        # at live receivers; receipt queues a broadcast; each sender's
        # quacked prefix rides what it sent
        new = np.arange(self.hi, hi_new)
        self.send_time[new] = t
        sent = new[up_s[self.osend[new]]]
        land = np.zeros((n_r, b - a), bool)
        land[self.orecv[sent], sent - a] = True
        heard = np.zeros((n_r, n_s), bool)
        heard[self.orecv[sent], self.osend[sent]] = True
        re_l, re_k = np.nonzero(resend)
        land[re_target[re_l, re_k], re_k] = True
        heard[re_target[re_l, re_k], re_l] = True
        land &= up_r[:, None]
        heard &= up_r[:, None]
        self.recv[:, S] |= land
        self.bq[:, S] = land & ~self.bdone[:, S]
        fresh = ((self.deliver_time[S] < 0)
                 & self.recv[:, S][self.correct_r].any(axis=0))
        self.deliver_time[S][fresh] = t
        self.n_delivered += int(fresh.sum())
        qp_prev = a + _prefixes(quacked_prev)
        self.hq_reports = np.where(
            heard, np.maximum(self.hq_reports, qp_prev[None, :]),
            self.hq_reports)
        self.hi = hi_new

        # (4) acknowledgements from live receivers to the rotating sender
        top_hq = -np.sort(-self.hq_reports, axis=1)
        if p.hq <= n_s:
            self.ack_floor = np.maximum(self.ack_floor, top_hq[:, p.hq - 1])
        eff = self.recv[:, S] | (idx[None, :] < self.ack_floor[:, None])
        for j in np.flatnonzero(up_r):
            gap = ~eff[j]
            rank = np.cumsum(gap)
            cum = a + self._prefix(eff[j])
            over = np.flatnonzero(rank > p.phi)
            horizon = a + int(over[0]) if len(over) else m
            claim = (eff[j] & (idx < horizon)) | (idx < cum)
            held = np.flatnonzero(eff[j])
            top = a + int(held[-1]) + 1 if len(held) else a
            newc = gap & (idx < top) & (rank <= p.phi)
            l_ = (j + t) % n_s
            if self.last_cum[l_, j] == cum and cum < m:
                newc[cum - a] = True
            self.n_known[l_, S] += claim & ~self.known[l_, j, S]
            self.known[l_, j, S] |= claim
            rep = self.rep[l_, j, S] | (self.compl[l_, j, S] & newc)
            self.n_rep[l_, S] += rep & ~self.rep[l_, j, S]
            self.rep[l_, j, S] = rep
            self.compl[l_, j, S] = newc
            self.last_cum[l_, j] = cum

        # (5) QUACK bookkeeping and the round's counts
        quacked = self.n_known[:, S] >= p.quack
        newly = quacked & (self.quack_time[:, S] < 0)
        self.quack_time[:, S][newly] = t
        obs = self.obs
        lat = t - self.send_time[S][fresh]
        np.add.at(obs["latency_hist"], latency_bucket(lat), 1)
        in_flight = int(((idx < hi_new)
                         & (self.deliver_time[S] < 0)).sum())
        obs["occupancy_hwm"] = max(obs["occupancy_hwm"], in_flight)
        obs["gc_lag_hwm"] = max(obs["gc_lag_hwm"], hi_new - a)
        obs["quack_events"] += int(newly.sum())
        obs["loss_events"] += int(declared.sum())
        obs["resend_total"] += int(resend.sum())
        prefixes = a + _prefixes(quacked)
        return {"cross_msgs": len(sent) + int(resend.sum()),
                "intra_msgs": intra,
                "resends": int(resend.sum()),
                "acks": int(up_r.sum()),
                "delivered": self.n_delivered,
                "min_quack_prefix": int(prefixes[self.correct_s].min())
                if self.correct_s.any() else NO_CORRECT_SENDER}

    def retire_at(self, t_next: int) -> int:
        """Advance the GC frontier before round ``t_next``: only the
        receivers still up then hold back a message."""
        a, b = self.lo, min(self.hi + 1, self.m)
        S = slice(a, b)
        idx = np.arange(a, b)
        acking = _up(self.crash_r, t_next)
        ok = ((self.n_known[:, S] >= self.p.quack).all(axis=0)
              & (idx < self.hi) & ~self.bq[:, S].any(axis=0)
              & (self.recv[:, S]
                 | (idx[None, :] < self.ack_floor[:, None])
                 | ~acking[:, None]).all(axis=0))
        self.lo = a + self._prefix(ok)
        return self.lo


def run(config: dict, counts: np.ndarray, *, links: int, chained: bool,
        steps: int, chunk: int, quorum_short: int = 0,
        failures: Optional[dict] = None) -> List[CrashLink]:
    """Run every lane of a session under the crash placement
    ``failures`` (``{"crash_s": rounds, "crash_r": rounds}``, one round
    per replica, -1 = never; None: no replica crashes); returns the
    lanes' final states. Every lane has the same placement, so a fleet
    runs one lane and a chain all of them.
    """
    p = Protocol.from_config(config, quorum_short)
    failures = failures or {}
    crash_s = failures.get("crash_s", [-1] * p.n_s)
    crash_r = failures.get("crash_r", [-1] * p.n_r)
    ostep = np.repeat(np.arange(len(counts)), counts)
    n = links if chained else 1
    lanes = [CrashLink(p, ostep, crash_s, crash_r) for _ in range(n)]
    m = len(ostep)
    floors = [m] * n
    for lane in lanes:
        lane.history = {f: np.zeros(steps, np.int64) for f in ROUND_FIELDS}
    for t in range(steps):
        if chained and t % chunk == 0:
            floors = [m] + [lanes[i - 1].lo for i in range(1, n)]
        for lane, floor in zip(lanes, floors):
            for f, v in lane.step(t, floor).items():
                lane.history[f][t] = v
        if (t + 1) % chunk == 0 and t + 1 < steps:
            for lane in lanes:
                lane.frontiers.append(lane.retire_at(t + 1))
    for lane in lanes:
        lane.frontiers.append(lane.lo)        # the last chunk retires none
    return lanes
