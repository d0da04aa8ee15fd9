"""Device dispatches per thousand protocol rounds (the engine's
``chunk_dispatch_count`` delta over its rounds)."""


def read(ctx):
    runs = [r for r in ctx.runs if r.counters.get("rounds")]
    rounds = sum(r.counters["rounds"] for r in runs)
    if not rounds:
        return None
    return 1000.0 * sum(r.counters["dispatches"] for r in runs) / rounds
