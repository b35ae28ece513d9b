"""Replay host path (``core/jaxplan.py`` ``_run_lowered``): the members'
``exec`` spans of a batched pass summed, per pass: each member's staging,
ledger replay and output split from its slice of the batched dispatch; the
mean over the traced window's passes.  None where a ``jit_replay`` span lies
inside an ``exec`` of the window, since a member that replays on its own
puts device time into its ``exec``."""
from chipbench.spans import per_call_ms


def _inside(spans, name, calls):
    return [(s["t0"], s["t1"]) for s in spans
            if s["name"] == name and s["t1"] is not None
            and any(t0 <= s["t0"] and s["t1"] <= t1 for t0, t1 in calls)]


def read(ctx):
    calls = ctx["calls"]
    execs = _inside(ctx["spans"], "exec", calls)
    replays = _inside(ctx["spans"], "jit_replay", calls)
    if any(e0 <= r0 and r1 <= e1 for r0, r1 in replays for e0, e1 in execs):
        return None
    return per_call_ms(ctx, "exec")
