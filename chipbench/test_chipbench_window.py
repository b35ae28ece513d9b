"""The window's arithmetic on a fake clock, and the end-to-end readers."""
import pytest

from chipbench import spec
from chipbench.window import closed_loop


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _loop(durations, seconds, between=0.0):
    """A closed loop whose calls take ``durations`` in turn; ``between``
    advances the clock after each call returns, as the loop's own work."""
    clock = FakeClock()

    def call(i):
        clock.t += durations[i % len(durations)]

    def ticking():
        t = clock.t
        clock.t += between
        return t
    return closed_loop(call, seconds, clock=ticking)


def test_window_ends_on_the_first_call_past_the_limit():
    calls, elapsed = _loop([3.0], 10.0)
    assert len(calls) == 4            # 3, 6, 9, 12: the fourth ends past 10
    assert elapsed == pytest.approx(12.0)
    assert [t1 - t0 for t0, t1 in calls] == [3.0] * 4


def test_time_between_calls_counts_in_the_window():
    calls, elapsed = _loop([1.0, 2.0], 5.0, between=0.25)
    # every clock read costs 0.25 s: latency 1.25 or 2.25, and the loop's
    # reads between calls lie inside the window as well
    assert [round(t1 - t0, 9) for t0, t1 in calls] == [1.25, 2.25, 1.25]
    assert elapsed == pytest.approx(calls[-1][1] - calls[0][0] + 0.25)
    assert elapsed >= sum(t1 - t0 for t0, t1 in calls)


def test_a_call_longer_than_the_window_is_not_cut_off():
    calls, elapsed = _loop([25.0], 10.0)
    assert len(calls) == 1 and elapsed == pytest.approx(25.0)


def test_end_to_end_readers():
    rate = spec.metric_reader("rows_per_s")
    p95 = spec.metric_reader("shuffle_p95_ms")
    setup = spec.metric_reader("setup_s")
    calls = [(float(i), i + 0.010 + 0.001 * (i % 20)) for i in range(100)]
    ctx = {"calls": calls, "completed": 100, "elapsed": 50.0,
           "work": {"rows_in": 1_000}, "setup_s": 7.5}
    assert rate(ctx) == pytest.approx(100 * 1_000 / 50.0)
    assert p95(ctx) == pytest.approx(10.0 + 19 * 0.95, abs=1e-6)
    assert setup(ctx) == 7.5


def test_max_calls_ends_the_window_first():
    calls, elapsed = closed_loop(lambda i: None, 10.0, clock=FakeClock(),
                                 max_calls=1)
    assert len(calls) == 1 and elapsed == 0.0
