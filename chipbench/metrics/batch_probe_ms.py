"""Admission (``core/service.py`` ``run_pending``): the program's
``batch_probe`` span, the grouping of a pass's submissions by batch
signature (plan peeks, stats signatures, routing tables), per pass; the
mean over the traced window's passes.  None where the program has no such
span."""
from chipbench.spans import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "batch_probe")
