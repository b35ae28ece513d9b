"""Replay host path (``core/jaxplan.py`` ``prepare_batch``): the program's
``stage_batch`` span, the host concatenation and stacking of a batched
dispatch's members, per pass; the mean over the traced window's passes.
None where the program has no such span."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "stage_batch")
