"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Device planes are ``/device:TPU:<n>``.  Busy time is the union of the
intervals of the device's operations (the ``XLA Ops`` line, or ``XLA
Modules`` where a plane has no op line), clipped to the harness's window
(from its start marker to its end marker) and averaged over the chips that
ran anything.  An idle gap is a stretch of the window in which no chip ran
an operation; it is named by the harness annotation the host was in at its
middle (``default_label`` where none was) and by the innermost other host
event there (what JAX was doing), so the longest gaps say what held the
device back.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
HARNESS_PREFIX = "chipbench."


def union(intervals):
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def start_marker(window_name: str) -> str:
    return window_name + ".start"


def end_marker(window_name: str) -> str:
    return window_name + ".end"


def reduce_events(device_ops: dict, host_events: list, window_name: str,
                  call_name: str, default_label: str = "between_calls"
                  ) -> dict | None:
    """The reduction proper, on plain data.

    ``device_ops``: chip -> [(op name, start ns, end ns)];
    ``host_events``: [(name, start ns, end ns)] of host threads, harness
    annotations among them, the window's markers too (an annotation that is
    still open when the trace stops is not recorded, so the window is not
    one).  Returns None when the trace holds no window or no device
    operation inside it."""
    starts = [s for n, s, _ in host_events if n == start_marker(window_name)]
    ends = [e for n, _, e in host_events if n == end_marker(window_name)]
    if not starts or not ends:
        return None
    lo, hi = min(starts), max(ends)
    per_chip = {}
    op_time: dict[str, float] = {}
    for chip, ops in device_ops.items():
        busy = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        if busy:
            per_chip[chip] = busy
        for name, s, e in ops:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_time[name] = op_time.get(name, 0.0) + c * 1e-9
    if not per_chip:
        return None
    busy_s = sum(sum(e - s for s, e in b) for b in per_chip.values()) \
        / len(per_chip) * 1e-9
    any_busy = union([iv for b in per_chip.values() for iv in b])
    gaps, cur = [], lo
    for s, e in any_busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    harness = [(n, s, e) for n, s, e in host_events
               if n.startswith(HARNESS_PREFIX) and not n.startswith(window_name)]
    jax_events = [(n, s, e) for n, s, e in host_events
                  if not n.startswith(HARNESS_PREFIX)]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        outer = [(ee - ss, n) for n, ss, ee in harness if ss <= mid < ee]
        inner = [(ee - ss, n) for n, ss, ee in jax_events if ss <= mid < ee]
        label = min(outer)[1] if outer else default_label
        if inner:
            label += "/" + min(inner)[1]
        named.append((label, (e - s) * 1e-9))
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])
    calls = [(s, e) for n, s, e in host_events
             if n == call_name and s >= lo and e <= hi]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "chips": len(per_chip),
        "calls": len(calls),
        "device_ops": [[n, t] for n, t in ops_sorted[:10]],
        "idle_gaps": [[n, t] for n, t in named[:10]],
    }


def read_xspace(path: str):
    """(device ops per chip, host events) from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_events = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is not None:
                device_ops[plane.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events)
    return device_ops, host_events


def reduce_dir(log_dir: str, window_name: str, call_name: str,
               default_label: str = "between_calls") -> dict | None:
    """Reduce the newest trace the profiler wrote under ``log_dir``."""
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        return None
    device_ops, host_events = read_xspace(files[-1])
    return reduce_events(device_ops, host_events, window_name, call_name,
                         default_label)
