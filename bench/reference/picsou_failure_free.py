"""Plain numpy reference of PICSOU streaming links: no faults, unit stakes.

Written from the protocol's description (§4.1–§4.3 of the paper: round-
robin sends with per-sender receiver rotation, intra-RSM broadcast,
cumulative + phi-list acknowledgements to a rotating sender, QUACK at
u_r + 1 acks, loss declared at r_r + 1 repeated complaints with the
retransmitter elected by rotation, the highest-quacked floor at r_s + 1
reports, and the GC retirement rule at chunk boundaries). It imports
nothing of the system under test and keeps one plain dense array per
quantity over the whole stream, touching only the live span of
messages each round.

One synchronous round ``t`` of a link:

1. last round's queued broadcasts land at every other receiver;
2. messages with a loss quorum (complaints repeated to a sender by
   receivers holding ``dup`` stake, not yet quacked) are retried; the
   a-th retry is sent by sender ``(k + a) mod n_s`` to receiver
   ``(orig_recv[k] + a) mod n_r``;
3. messages due by ``t`` (and below the link's commit floor) are sent
   once to their receiver, land, and queue a broadcast; a message is
   delivered the first round a receiver holds it; each sender
   piggybacks its quacked prefix on what it sent;
4. receiver ``j`` acks sender ``(j + t) mod n_s``: its cumulative count,
   its claims below the (phi+1)-th gap, and its first phi gaps below
   its highest held message, plus a complaint about ``cum`` when the
   count did not move since its last ack to that sender;
5. a message is quacked at a sender once u_r + 1 receivers' claims
   reached it.

At the end of every chunk but the last, the link retires the leading
messages that are dispatched, quacked at every sender, held by every
receiver (or below its floor) and have no broadcast queued. A chained
link may only send message ``k`` once its upstream link has retired it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from bench.record import ROUND_FIELDS, SessionRecord

# power-of-two latency buckets of the live telemetry: bucket 0 holds a
# latency of 0 rounds, bucket i holds [2^(i-1), 2^i), the last is open
LATENCY_BUCKETS = 18


def latency_bucket(lat: np.ndarray) -> np.ndarray:
    edges = 2 ** np.arange(LATENCY_BUCKETS - 1)
    return (np.asarray(lat)[..., None] >= edges).sum(axis=-1)


@dataclasses.dataclass(frozen=True)
class Protocol:
    """The quorum sizes and widths of one link, from the configuration."""

    n_s: int
    n_r: int
    quack: int        # u_r + 1 receivers' claims form a QUACK
    dup: int          # r_r + 1 repeated complaints declare a loss
    hq: int           # r_s + 1 sender reports set a receiver's floor
    phi: int

    @classmethod
    def from_config(cls, config: dict, quorum_short: int = 0) -> "Protocol":
        s, r = config["sender"], config["receiver"]
        return cls(n_s=s["n"], n_r=r["n"],
                   quack=r["u"] + 1 - quorum_short,
                   dup=max(r["r"] + 1, 1), hq=max(s["r"] + 1, 1),
                   phi=config["protocol"]["phi"])


class Link:
    """One link's whole state, dense over the stream of ``m`` messages."""

    def __init__(self, p: Protocol, ostep: np.ndarray):
        self.p = p
        m = self.m = len(ostep)
        n_s, n_r = p.n_s, p.n_r
        k = np.arange(m)
        self.ostep = np.asarray(ostep, dtype=np.int64)
        self.osend = k % n_s
        self.orecv = (k % n_s + k // n_s) % n_r
        self.recv = np.zeros((n_r, m), bool)
        self.bq = np.zeros((n_r, m), bool)
        self.bdone = np.zeros((n_r, m), bool)
        self.known = np.zeros((n_s, n_r, m), bool)
        self.compl = np.zeros((n_s, n_r, m), bool)
        self.rep = np.zeros((n_s, n_r, m), bool)
        self.n_known = np.zeros((n_s, m), np.int32)
        self.n_rep = np.zeros((n_s, m), np.int32)
        self.last_cum = np.full((n_s, n_r), -1, np.int64)
        self.retry = np.zeros((n_s, m), np.int32)
        self.quack_time = np.full((n_s, m), -1, np.int32)
        self.deliver_time = np.full(m, -1, np.int32)
        self.send_time = np.full(m, -1, np.int64)
        self.hq_reports = np.zeros((n_r, n_s), np.int64)
        self.ack_floor = np.zeros(n_r, np.int64)
        self.history: Dict[str, np.ndarray] = {}
        self.frontiers: List[int] = []
        self.lo = 0              # retired prefix (GC frontier)
        self.hi = 0              # dispatched prefix
        self.n_delivered = 0
        self.obs = {"latency_hist": np.zeros(LATENCY_BUCKETS, np.int64),
                    "occupancy_hwm": 0, "gc_lag_hwm": 0,
                    "quack_events": 0, "loss_events": 0,
                    "resend_total": 0, "uncounted": 0}

    def _prefix(self, ok: np.ndarray) -> int:
        """Leading run of True in ``ok``."""
        bad = np.flatnonzero(~ok)
        return int(bad[0]) if len(bad) else len(ok)

    def step(self, t: int, floor: int) -> Dict[str, int]:
        p, m = self.p, self.m
        n_s, n_r = p.n_s, p.n_r
        arrived = int(np.searchsorted(self.ostep, t, side="right"))
        hi_new = max(self.hi, min(floor, arrived))
        a, b = self.lo, min(hi_new + 1, m)
        idx = np.arange(a, b)
        S = slice(a, b)

        # (1) queued broadcasts land at every other receiver
        bq = self.bq[:, S]
        n_bq = bq.sum(axis=0)
        intra = int(n_bq.sum()) * (n_r - 1)
        self.recv[:, S] |= (n_bq[None, :] - bq) > 0
        self.bdone[:, S] |= bq

        # (2) loss declaration and retransmission, from last round's
        # knowledge; only for messages already sent
        quacked_prev = self.n_known[:, S] >= p.quack
        declared = ((self.n_rep[:, S] >= p.dup) & ~quacked_prev
                    & (idx < self.hi)[None, :])
        resend = np.zeros_like(declared)
        re_target = np.zeros(declared.shape, np.int64)
        if declared.any():
            self.retry[:, S] += declared
            retry = self.retry[:, S]
            resend = declared & ((idx[None, :] + retry) % n_s
                                 == np.arange(n_s)[:, None])
            re_target = (self.orecv[S][None, :] + retry) % n_r
            for l_, k_ in zip(*np.nonzero(declared)):
                self.compl[l_, :, a + k_] = False
                self.rep[l_, :, a + k_] = False
                self.n_rep[l_, a + k_] = 0

        # (3) original sends land at their receiver; receipt queues a
        # broadcast; each sender's quacked prefix rides what it sent
        new = np.arange(self.hi, hi_new)
        self.send_time[new] = t
        land = np.zeros((n_r, b - a), bool)
        land[self.orecv[new], new - a] = True
        heard = np.zeros((n_r, n_s), bool)
        heard[self.orecv[new], self.osend[new]] = True
        for l_, k_ in zip(*np.nonzero(resend)):
            land[re_target[l_, k_], k_] = True
            heard[re_target[l_, k_], l_] = True
        self.recv[:, S] |= land
        self.bq[:, S] = land & ~self.bdone[:, S]
        fresh = (self.deliver_time[S] < 0) & self.recv[:, S].any(axis=0)
        self.deliver_time[S][fresh] = t
        self.n_delivered += int(fresh.sum())
        qp_prev = np.array([a + self._prefix(quacked_prev[l_])
                            for l_ in range(n_s)])
        self.hq_reports = np.where(
            heard, np.maximum(self.hq_reports, qp_prev[None, :]),
            self.hq_reports)
        self.hi = hi_new

        # (4) acknowledgements to the rotating sender
        top_hq = -np.sort(-self.hq_reports, axis=1)
        if p.hq <= n_s:
            self.ack_floor = np.maximum(self.ack_floor, top_hq[:, p.hq - 1])
        eff = self.recv[:, S] | (idx[None, :] < self.ack_floor[:, None])
        for j in range(n_r):
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
        return {"cross_msgs": len(new) + int(resend.sum()),
                "intra_msgs": intra,
                "resends": int(resend.sum()),
                "acks": n_r,
                "delivered": self.n_delivered,
                "min_quack_prefix": min(a + self._prefix(quacked[l_])
                                        for l_ in range(n_s))}

    def retire(self) -> int:
        """Advance the GC frontier past every retirable leading message."""
        a, b = self.lo, min(self.hi + 1, self.m)
        S = slice(a, b)
        idx = np.arange(a, b)
        ok = ((self.n_known[:, S] >= self.p.quack).all(axis=0)
              & (idx < self.hi) & ~self.bq[:, S].any(axis=0)
              & (self.recv[:, S]
                 | (idx[None, :] < self.ack_floor[:, None])).all(axis=0))
        self.lo = a + self._prefix(ok)
        return self.lo


def run(config: dict, counts: np.ndarray, *, links: int, chained: bool,
        steps: int, chunk: int, quorum_short: int = 0) -> List[Link]:
    """Run every lane of a session; returns the lanes' final states.

    Independent lanes of one workload are identical, so a fleet runs one
    lane and a chain all of them; the caller compares every program lane
    against them.
    """
    p = Protocol.from_config(config, quorum_short)
    ostep = np.repeat(np.arange(len(counts)), counts)
    n = links if chained else 1
    lanes = [Link(p, ostep) for _ in range(n)]
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
                lane.frontiers.append(lane.retire())
    for lane in lanes:
        lane.frontiers.append(lane.lo)        # the last chunk retires none
    return lanes


def lane_of(lanes: List[Link], b: int, chained: bool) -> Link:
    return lanes[b] if chained else lanes[0]


def as_record(lanes: List[Link], *, links: int, chained: bool, chunk: int,
              steps: int, sample_lane: int) -> SessionRecord:
    """The record a program session would give if it equalled ``lanes``
    (the control puts this in the program's place)."""
    per = [lane_of(lanes, b, chained) for b in range(links)]
    ends = np.minimum(np.arange(1, len(per[0].frontiers) + 1) * chunk,
                      steps)
    bases = np.stack([np.asarray(x.frontiers) for x in per], axis=1)
    rounds = {f: np.stack([x.history[f] for x in per])
              for f in ROUND_FIELDS}
    s = per[sample_lane]
    messages = {"quack_time": s.quack_time.copy(),
                "deliver_time": s.deliver_time.copy(),
                "retry": s.retry.copy(), "recv_has": s.recv.copy()}
    obs = [{k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in x.obs.items()} for x in per]
    delivered = sum(x.n_delivered for x in per)
    retired = sum(x.lo for x in per)
    return SessionRecord(
        chunk_ends=ends, bases=bases, rounds=rounds, lane=sample_lane,
        messages=messages, obs=obs,
        live={"t": int(ends[-1]), "delivered": delivered,
              "retired": retired,
              "resends": sum(x.obs["resend_total"] for x in per),
              "losses": sum(x.obs["loss_events"] for x in per)},
        delivered=delivered, retired=retired)
