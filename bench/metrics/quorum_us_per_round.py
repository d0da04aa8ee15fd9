"""Device self time of the protocol step's stake quorums (the
``c3b_quorum`` scope: both ``stake_quorum_bitmap`` calls and the QUACK
stamp) in the traced session, over the protocol rounds it ran, in
microseconds."""

from bench import scopes


def read(ctx):
    red = scopes.of(ctx)
    if red is None or not red.scoped or not ctx.traced_rounds:
        return None
    return red.scope_s.get("c3b_quorum", 0.0) / ctx.traced_rounds * 1e6
