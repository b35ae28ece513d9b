"""Jitted replay (``core/jaxplan.py``): the device's busy time in the traced
window (union of its operations' intervals) per shuffle the window ran."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["calls"]:
        return None
    return tr["busy_s"] / tr["calls"] * 1e3
