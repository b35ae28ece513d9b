"""The per-layer readers of the program's spans (``chipbench/spans.py``):
a mean per call over the spans inside each traced call, nothing where no
call holds one."""
import pytest

from chipbench import spec


def _span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


# two calls; each holds an exec span with its jit_replay, to_device and
# to_host inside it; one span of each name lies outside every call
SPAN_CALLS = [(0.0, 0.040), (0.050, 0.100)]
SPANS = [
    _span("exec", 0.010, 0.035), _span("to_device", 0.011, 0.013),
    _span("jit_replay", 0.013, 0.030), _span("to_host", 0.030, 0.033),
    _span("exec", 0.055, 0.099), _span("to_device", 0.056, 0.060),
    _span("jit_replay", 0.060, 0.090), _span("to_host", 0.090, 0.096),
    _span("exec", 0.200, 0.300), _span("to_device", 0.200, 0.250),
    _span("jit_replay", 0.250, 0.290), _span("to_host", 0.290, 0.299),
    _span("jit_replay", 0.035, 0.055),         # straddles a call's end
]


@pytest.mark.parametrize("metric, per_call", [
    ("replay_span_ms", (17, 30)),
    ("to_device_ms", (2, 4)),
    ("to_host_ms", (3, 6)),
    ("exec_host_ms", (25 - 17, 44 - 30)),
])
def test_span_readers_average_the_spans_inside_each_call(metric, per_call):
    read = spec.metric_reader(metric)
    ctx = {"calls": SPAN_CALLS, "spans": SPANS}
    assert read(ctx) == pytest.approx(sum(per_call) / 2)
    # the split name reads with the same file
    assert spec.metric_reader(metric + ".part")(ctx) == read(ctx)
    # a span open at the end of the trace (t1 None) is no reading
    assert read(dict(ctx, spans=SPANS + [_span("exec", 0.001, None)])) == \
        read(ctx)


@pytest.mark.parametrize("metric", ["replay_span_ms", "to_device_ms",
                                    "to_host_ms", "exec_host_ms"])
def test_span_readers_read_nothing_without_a_span_in_a_call(metric):
    read = spec.metric_reader(metric)
    assert read({"calls": SPAN_CALLS, "spans": []}) is None
    outside = [s for s in SPANS if s["t0"] >= 0.2]
    assert read({"calls": SPAN_CALLS, "spans": outside}) is None
    assert read({"calls": [], "spans": SPANS}) is None
