"""Device self time of the protocol step's retransmission path (the
``c3b_retransmit`` scope: loss declaration, retransmitter election,
resend targets and landing) in the traced session, over the protocol
rounds it ran, in microseconds."""

from bench import scopes


def read(ctx):
    red = scopes.of(ctx)
    if red is None or not red.scoped or not ctx.traced_rounds:
        return None
    return red.scope_s.get("c3b_retransmit", 0.0) / ctx.traced_rounds * 1e6
