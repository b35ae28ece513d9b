"""Jitted replay (``core/jaxplan.py``): the program's ``jit_replay`` span,
from dispatch until the outputs are ready on the device, per shuffle; the
mean over the traced window's calls."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "jit_replay")
