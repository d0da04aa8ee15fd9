"""The chip's peaks and the least bytes a protocol round must move.

The least bytes of a round are reckoned from the cell's numbers alone
(links, the two cluster sizes and the yardstick window ``W`` pinned in
the configuration file), so they stay the same work whatever layout a
later change of the program picks: the carried window state, read once
and written once, with its ``(n_s, n_r, W)``, ``(n_r, W)`` and ``(W,)``
flags as bits and its ``(n_s, W)`` and ``(W,)`` round stamps as int32.
A round reads and writes at least that; nothing it computes is counted,
because the step is bound by memory, not by operations.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def window_cells(n_s: int, n_r: int, w: int) -> dict:
    """Cells of one link's carried window state, by kind."""
    return {"flags": 3 * n_s * n_r * w + 3 * n_r * w + w,
            "stamps": 2 * n_s * w + w}


def least_bytes_per_round(config: dict, links: int) -> float:
    cells = window_cells(config["sender"]["n"], config["receiver"]["n"],
                         int(config["window_slots"]))
    per_link = cells["flags"] / 8 + 4 * cells["stamps"]
    return 2.0 * links * per_link
