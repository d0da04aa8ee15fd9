"""Share of the engine's dispatches launched while the previous one was
still undrained (the ``launched_ahead`` counter over ``dispatches``):
how often the host loop's double buffering engaged."""


def read(ctx):
    runs = [r for r in ctx.runs if "launched_ahead" in r.counters]
    dispatches = sum(r.counters["dispatches"] for r in runs)
    if not dispatches:
        return None
    return 100.0 * sum(r.counters["launched_ahead"] for r in runs) \
        / dispatches
