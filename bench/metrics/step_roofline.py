"""Share of the memory roofline the protocol step reaches: the least
bytes a round must move (``bench/roofline.py``) at the chip's HBM
bandwidth, over the device time a round took. Bound by bytes."""

from bench import roofline


def read(ctx):
    if ctx.trace is None or not ctx.traced_rounds or not ctx.trace.busy_s:
        return None
    least = roofline.least_bytes_per_round(ctx.cell.config, ctx.cell.links)
    floor_s = least / roofline.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ctx.trace.busy_s / ctx.traced_rounds)
