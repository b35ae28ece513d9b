"""Replay host path (``core/jaxplan.py``): the program's ``to_device`` span,
the replay's inputs put on the device, per shuffle; the mean over the traced
window's calls."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "to_device")
