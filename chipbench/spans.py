"""Per-call readings of the program's own spans (``FlightRecorder``, on
with ``tracing=True``), which the per-layer metrics of the replay read.

The spans and the harness's calls are both on ``time.monotonic``.  The
program records its spans whatever the profiler does, so a reading covers
every call of the traced window, also where the profiler stopped early."""


def _intervals(ctx, name):
    return [(s["t0"], s["t1"]) for s in ctx["spans"]
            if s["name"] == name and s["t1"] is not None]


def per_call_ms(ctx, name, minus=None):
    """The mean over the traced window's calls of the time of the spans
    named ``name`` inside each call, less that of the spans named ``minus``
    there, in ms; None where no call holds a span named ``name``."""
    spans = _intervals(ctx, name)
    less = _intervals(ctx, minus) if minus else []
    per_call = []
    for t0, t1 in ctx["calls"]:
        inside = [e - s for s, e in spans if s >= t0 and e <= t1]
        if inside:
            taken = sum(e - s for s, e in less if s >= t0 and e <= t1)
            per_call.append((sum(inside) - taken) * 1e3)
    return sum(per_call) / len(per_call) if per_call else None
