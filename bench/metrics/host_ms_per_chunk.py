"""Host time per drained chunk not spent waiting on the device:
(session wall time - the ``drain_wait`` spans) / chunks drained, over
the window sessions the profiler did not slow."""


def read(ctx):
    runs = [r for r in ctx.steady_runs if r.record is not None]
    chunks = sum(len(r.record.chunk_ends) for r in runs)
    if not chunks:
        return None
    busy_ns = sum((r.end_ns - r.start_ns)
                  - sum(s.dur_ns for s in r.spans if s.name == "drain_wait")
                  for r in runs)
    return busy_ns / 1e6 / chunks
