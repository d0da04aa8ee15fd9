"""What one session produced, in the one form that the program's side
and the reference's side of the check both take."""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

ROUND_FIELDS = ("cross_msgs", "intra_msgs", "resends", "acks",
                "delivered", "min_quack_prefix")
OBS_FIELDS = ("latency_hist", "occupancy_hwm", "gc_lag_hwm",
              "quack_events", "loss_events", "resend_total", "uncounted")
LIVE_FIELDS = ("t", "delivered", "retired", "resends", "losses")


@dataclasses.dataclass
class SessionRecord:
    """What one session produced, in the form both sides are compared.

    ``chunk_ends`` and ``bases`` hold, per drained chunk, its end round
    and every lane's retired prefix after it; ``rounds`` every lane's per-round
    counts; ``messages`` the per-message outcomes of one lane;
    ``obs`` every lane's session totals; ``live`` the session's last
    live-telemetry row.
    """

    chunk_ends: np.ndarray                 # (n_chunks,)
    bases: np.ndarray                      # (n_chunks, lanes)
    rounds: Dict[str, np.ndarray]          # field -> (lanes, steps)
    lane: int
    messages: Dict[str, np.ndarray]        # quack_time, deliver_time, ...
    obs: List[Dict[str, object]]           # per lane
    live: Dict[str, float]
    delivered: int
    retired: int
