"""Jitted replay: the least time the chip's HBM bandwidth allows for one
shuffle (every input row read once, every output row written once, at the
wire width: ``work.min_hbm_bytes``) over the device time per shuffle, in %.
The work is fixed by the cell's shapes, whatever implements the replay."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["calls"] or tr["busy_s"] <= 0:
        return None
    least_s = ctx["work"]["min_hbm_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return least_s / (tr["busy_s"] / tr["calls"]) * 100.0
