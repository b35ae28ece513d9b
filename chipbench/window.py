"""The measured window of a closed loop."""
from __future__ import annotations

import time


def closed_loop(call, seconds: float, clock=time.monotonic,
                max_calls: int | None = None):
    """Call ``call(i)`` for i = 0, 1, ... back to back, as one client that
    waits for each answer before it sends the next request.

    The window starts as the first call starts.  It ends when the first call
    that finishes after ``seconds`` have elapsed completes (or the
    ``max_calls``-th call, where that comes first), so no call is cut off
    and none is dropped; the client's own work between calls lies inside
    it.  Returns each call's (start, end) and the window's length."""
    start = clock()
    calls = []
    while True:
        t0 = clock()
        call(len(calls))
        t1 = clock()
        calls.append((t0, t1))
        if t1 - start >= seconds or len(calls) == max_calls:
            return calls, t1 - start
