#!/usr/bin/env python3
"""The control of the check, at a cell's own size.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The cell's plain reference, computed with its QUACK quorum one receiver
short (u instead of u + 1 acks: the cheaper quorum that would tempt a
later change, and a break of the configuration's ``quack_quorum``
guarantee), is put in the program's place: its sessions go through the
same comparison a run's sessions go through, against the sound
reference, with the lanes sampled from each seed as a run samples them.
The check must come out not correct. Where the configuration carries
failures, both references run under the seed's crash placement, as a
run's check does. The benchmark's own runs never run this; it prints
one JSON line per seed with every number compared.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compare  # noqa: E402
from bench.harness import placement, reference  # noqa: E402
from bench.window import SessionRun  # noqa: E402
from bench.workload import arrivals, drain_rounds, find_cell  # noqa: E402

SESSIONS = 4      # sessions compared per seed, as a short window has


def readings(cell, seed: int) -> dict:
    """The numbers the check compares for the control, and for the sound
    reference in the program's place, on ``seed``'s sampled lanes."""
    counts = arrivals(cell.traffic, seed)
    steps = len(counts) + drain_rounds(cell.config, cell.traffic)
    ref = reference(cell.config)
    kw = dict(links=cell.links, chained=cell.chained, steps=steps,
              chunk=cell.chunk_steps)
    failures = placement(cell, seed)
    crashes = {} if failures is None else {"failures": failures}
    sound = ref.run(cell.config, counts, **kw, **crashes)
    short = ref.run(cell.config, counts, quorum_short=1, **kw, **crashes)
    rng = np.random.default_rng(seed)
    lanes = [int(rng.integers(cell.links)) for _ in range(SESSIONS)]
    offered = cell.horizon * cell.links

    def check(lanes_state):
        runs = [SessionRun(0, 0, lane,
                           ref.as_record(lanes_state, sample_lane=lane,
                                         **kw),
                           counters={"traces": 0})
                for lane in lanes]
        numbers = compare.checks(
            runs, lambda lane: ref.as_record(sound, sample_lane=lane, **kw),
            offered)
        return {"correct": compare.passed(numbers), "checks": numbers}

    out = check(short)
    out["sound"] = check(sound)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    for seed in args.seeds:
        r = readings(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_correct": r["correct"],
                          "control": r["checks"],
                          "sound": r["sound"]["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
