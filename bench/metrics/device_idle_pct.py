"""Share of the traced session in which no operation ran on the device
(1 - union of device op intervals / the session's length)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
