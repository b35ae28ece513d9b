"""The trace reduction: busy union, clipping to the window, idle gaps named
by the host span they fell in, the per-layer readers on top of it."""
import pytest

from chipbench import spec, trace

MS = 1_000_000      # ns


def _recorded():
    """A window of two calls: ops overlap inside the first call, the host
    stages between them, and an op runs past the window's end."""
    host = [
        ("chipbench.window.start", 0, 0),
        ("chipbench.window.end", 100 * MS, 100 * MS),
        ("chipbench.shuffle", 0, 40 * MS),
        ("PjitFunction(entry)", 1 * MS, 3 * MS),
        ("chipbench.shuffle", 50 * MS, 100 * MS),
        ("TransferToDevice", 50 * MS, 58 * MS),
    ]
    ops = {"/device:TPU:0": [
        ("sort.1", 5 * MS, 20 * MS),
        ("fusion.2", 15 * MS, 30 * MS),      # overlaps sort.1
        ("while.3", 60 * MS, 90 * MS),
        ("while.3", 95 * MS, 120 * MS),      # clipped at 100
    ]}
    return ops, host


def test_busy_is_the_union_clipped_to_the_window():
    ops, host = _recorded()
    r = trace.reduce_events(ops, host, "chipbench.window", "chipbench.shuffle")
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx((25 + 30 + 5) * 1e-3)
    assert r["calls"] == 2 and r["chips"] == 1
    assert r["device_ops"][0] == ["while.3", pytest.approx(0.035)]


def test_idle_gaps_are_named_by_the_host_span():
    ops, host = _recorded()
    r = trace.reduce_events(ops, host, "chipbench.window", "chipbench.shuffle")
    gaps = dict((n, t) for n, t in r["idle_gaps"])
    # 30-60 ms: its middle (45) lies between the two calls
    assert gaps["between_calls"] == pytest.approx(0.030)
    # 0-5 ms: inside the first call, JAX dispatching
    assert gaps["chipbench.shuffle/PjitFunction(entry)"] == pytest.approx(0.005)
    # 90-95 ms: inside the second call, no JAX event around
    assert gaps["chipbench.shuffle"] == pytest.approx(0.005)
    assert [t for _, t in r["idle_gaps"]] == sorted(
        (t for _, t in r["idle_gaps"]), reverse=True)


def test_no_window_or_no_device_op_reads_nothing():
    ops, host = _recorded()
    assert trace.reduce_events(ops, host[1:], "chipbench.window", "x") is None
    assert trace.reduce_events({}, host, "chipbench.window", "x") is None
    assert trace.reduce_events({"/device:TPU:0": [("a", 200 * MS, 300 * MS)]},
                               host, "chipbench.window", "x") is None


def test_union():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def test_per_layer_readers():
    ops, host = _recorded()
    tr = trace.reduce_events(ops, host, "chipbench.window", "chipbench.shuffle")
    ctx = {"trace": tr, "peaks": {"hbm_bytes_per_s": 819e9},
           "work": {"min_hbm_bytes": 30_000_000},
           "calls": [(0.0, 0.040), (0.050, 0.100)],
           "spans": [{"name": "exec", "t0": 0.010, "t1": 0.035},
                     {"name": "exec", "t0": 0.055, "t1": 0.099},
                     {"name": "plan_lookup", "t0": 0.001, "t1": 0.002}]}
    read = {m: spec.metric_reader(m) for m in (
        "replay_device_ms", "replay_hbm_roofline", "device_idle_share",
        "front_end_ms")}
    assert read["replay_device_ms"](ctx) == pytest.approx(30.0)
    assert read["replay_hbm_roofline"](ctx) == pytest.approx(
        30e6 / 819e9 / 0.030 * 100)
    assert read["device_idle_share"](ctx) == pytest.approx(40.0)
    assert read["front_end_ms"](ctx) == pytest.approx(((40 - 25) + (50 - 44)) / 2)
    assert all(read[m](dict(ctx, trace=None)) is None
               for m in ("replay_device_ms", "replay_hbm_roofline",
                         "device_idle_share"))
    assert read["front_end_ms"](dict(ctx, spans=[])) is None


def test_a_cpu_trace_has_host_events_and_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1000.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window.start"):
        pass
    with jax.profiler.TraceAnnotation("chipbench.shuffle"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("chipbench.window.end"):
        pass
    jax.profiler.stop_trace()
    import glob
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    ops, host = trace.read_xspace(path)
    assert ops == {}
    assert {"chipbench.window.start", "chipbench.window.end",
            "chipbench.shuffle"} <= {n for n, _, _ in host}
    assert trace.reduce_dir(str(tmp_path), "chipbench.window",
                            "chipbench.shuffle") is None


def test_a_trace_cut_inside_a_call_names_its_gaps_by_the_call():
    """A trace ended by the timer inside a call holds no event of that call:
    its gaps take the label the harness gives for that case."""
    ops, host = _recorded()
    host = [h for h in host if h[0] != "chipbench.shuffle"]
    r = trace.reduce_events(ops, host, "chipbench.window", "chipbench.shuffle",
                            default_label="chipbench.shuffle")
    assert r["calls"] == 0
    assert r["idle_gaps"][0] == ["chipbench.shuffle", pytest.approx(0.030)]
    assert not any(n.startswith("between_calls") for n, _ in r["idle_gaps"])


def test_a_timer_ends_the_trace_inside_a_long_call(monkeypatch):
    import time

    import jax

    from chipbench import run

    stops = []
    real = jax.profiler.stop_trace

    def stop_trace():
        stops.append(time.monotonic())
        real()
    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)

    class SlowCall:
        def window(self, seconds, max_calls=None):
            t0 = time.monotonic()
            time.sleep(0.6)
            return [(t0, time.monotonic())], time.monotonic() - t0

    calls, elapsed, tr = run.traced_window(SlowCall(), 10.0, 1, 0.1)
    assert len(stops) == 1 and stops[0] < calls[0][1] - 0.3
    assert tr is None                   # the CPU has no device plane
    # without the timer the trace ends with the window
    stops.clear()
    calls, _, _ = run.traced_window(SlowCall(), 10.0, 1, None)
    assert len(stops) == 1 and stops[0] >= calls[0][1]
