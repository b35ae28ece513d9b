"""Service front end (``core/service.py``, ``core/plancache.py``): per
shuffle, the client's call time minus the program's ``exec`` span, both on
``time.monotonic``; the mean over the traced window's calls."""


def read(ctx):
    execs = [(s["t0"], s["t1"]) for s in ctx["spans"]
             if s["name"] == "exec" and s["t1"] is not None]
    per_call = []
    for t0, t1 in ctx["calls"]:
        inside = sum(e - s for s, e in execs if s >= t0 and e <= t1)
        if inside > 0:
            per_call.append((t1 - t0 - inside) * 1e3)
    return sum(per_call) / len(per_call) if per_call else None
