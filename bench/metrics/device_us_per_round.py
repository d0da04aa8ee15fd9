"""Device busy time of the traced session over the protocol rounds it
ran (all lanes step together), in microseconds."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds:
        return None
    return ctx.trace.busy_s / ctx.traced_rounds * 1e6
