#!/usr/bin/env python3
"""The benchmark of the served stream path, one cell per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs from the root of a checkout on a machine with a TPU; there is no
CPU fallback. With ``--trace 0`` the result reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from
spans, counters and a device trace of one window session. The last
lines of standard error are the numbers the check compared, each with
its limit; the last line of standard output is the result as one JSON
object.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):       # the system under test, this package
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# JAX's persistent compilation cache, at one fixed path inside the
# checkout (the path is part of each entry's key); set before JAX is
# imported, so the program takes this directory and no other
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench import harness  # noqa: E402
from bench.workload import find_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    try:
        device = harness.check_device(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    log(f"device: {device}")
    out = harness.run_cell(cell, args.seed, args.seconds,
                           bool(args.trace), T0, log=log)
    harness.main_result_lines(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
