"""Messages delivered per wall second, summed over links (every relay
hop counts), over the whole of every window session, drain tails
included; the harness's bookkeeping between sessions is left out."""


def read(ctx):
    delivered = sum(r.record.delivered for r in ctx.runs
                    if r.record is not None)
    return delivered / ctx.served_s
