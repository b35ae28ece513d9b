"""Replay host path (``core/jaxplan.py``): the program's ``to_host`` span,
the replay's outputs copied back to the host, per shuffle; the mean over the
traced window's calls."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "to_host")
