"""Host time the session's drain sink takes per drained chunk: the
program's ``sink_chunk`` spans over the chunks drained, in the window
sessions the profiler did not slow, in microseconds."""


def read(ctx):
    runs = [r for r in ctx.steady_runs if r.record is not None]
    spans = [s.dur_ns for r in runs for s in r.spans
             if s.name == "sink_chunk"]
    chunks = sum(len(r.record.chunk_ends) for r in runs)
    if not spans or not chunks:
        return None
    return sum(spans) / 1e3 / chunks
