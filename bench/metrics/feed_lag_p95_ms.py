"""95th percentile, over every chunk drained in the window, of the wall
time from the start of the ``dispatch`` span that launched the chunk to
the session's sink receiving it: how stale the live feed is."""

import numpy as np

LAUNCH = ("dispatch", "compile")


def chunk_lags_ms(run):
    """Feed lag of each chunk of one session, in milliseconds."""
    launches = sorted((s.args["t"], s.start_ns) for s in run.spans
                      if s.name in LAUNCH)
    starts = [t for t, _ in launches]
    lags = []
    for t_end, got in zip(run.record.chunk_ends, run.arrived_ns):
        i = int(np.searchsorted(starts, t_end, side="left")) - 1
        lags.append((got - launches[i][1]) / 1e6)
    return lags


def read(ctx):
    lags = [x for r in ctx.runs if r.record is not None
            for x in chunk_lags_ms(r)]
    return float(np.percentile(lags, 95)) if lags else None
