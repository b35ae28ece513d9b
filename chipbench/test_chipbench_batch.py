"""The throughput-test cell (``q18_agg_batch4``): the readers of a batched
pass's spans, and the cell itself run tiny on the CPU through
``run.run_cell``, every shuffle a member of one batch of four."""
import numpy as np
import pytest

from chipbench import run, spec

TINY = {"lineitem": 6_000, "orders": 1_500, "part": 200, "customer": 150}


def _span(name, t0, t1):
    return {"name": name, "t0": t0, "t1": t1}


# two passes of two members each: a probe, a batched dispatch (its staging
# and device stages), then each member's exec; one span of each name lies
# outside every pass.  The window lists each pass once per member.
PASSES = [(0.0, 0.100), (0.200, 0.300)]
CALLS = [c for c in PASSES for _ in range(2)]
SPANS = [
    _span("batch_probe", 0.001, 0.003), _span("stage_batch", 0.004, 0.010),
    _span("to_device", 0.010, 0.020), _span("jit_replay", 0.020, 0.060),
    _span("to_host", 0.060, 0.070),
    _span("exec", 0.070, 0.080), _span("exec", 0.080, 0.095),
    _span("batch_probe", 0.201, 0.205), _span("stage_batch", 0.205, 0.213),
    _span("to_device", 0.213, 0.220), _span("jit_replay", 0.220, 0.250),
    _span("to_host", 0.250, 0.260),
    _span("exec", 0.260, 0.275), _span("exec", 0.275, 0.280),
    _span("batch_probe", 0.500, 0.600), _span("stage_batch", 0.500, 0.600),
    _span("exec", 0.500, 0.600),
]


@pytest.mark.parametrize("metric, per_pass", [
    ("batch_probe_ms", (2, 4)),
    ("batch_stage_ms", (6, 8)),
    ("member_exec_ms", (10 + 15, 15 + 5)),
    ("replay_span_ms.batch", (40, 30)),
    ("to_device_ms.batch", (10, 7)),
    ("to_host_ms.batch", (10, 10)),
])
def test_batch_readers_average_each_pass(metric, per_pass):
    read = spec.metric_reader(metric)
    ctx = {"calls": CALLS, "spans": SPANS}
    assert read(ctx) == pytest.approx(sum(per_pass) / 2)
    # each pass listed once per member reads as the pass listed once
    assert read(dict(ctx, calls=PASSES)) == pytest.approx(read(ctx))


@pytest.mark.parametrize("metric", ["batch_probe_ms", "batch_stage_ms",
                                    "member_exec_ms"])
def test_batch_readers_read_nothing_without_their_span(metric):
    """A program without the span (the parent of the change that added it)
    gives no reading, and no error."""
    read = spec.metric_reader(metric)
    assert read({"calls": CALLS, "spans": []}) is None
    outside = [s for s in SPANS if s["t0"] >= 0.5]
    assert read({"calls": CALLS, "spans": outside}) is None
    assert read({"calls": [], "spans": SPANS}) is None


def test_member_exec_reads_nothing_where_a_replay_is_inside_an_exec():
    read = spec.metric_reader("member_exec_ms")
    solo = SPANS + [_span("jit_replay", 0.262, 0.270)]
    assert read({"calls": CALLS, "spans": solo}) is None
    # one outside the window does not count
    later = SPANS + [_span("jit_replay", 0.510, 0.520)]
    assert read({"calls": CALLS, "spans": later}) == pytest.approx(22.5)


def test_the_throughput_test_config_runs_tpch_dc16s_data_in_four_streams():
    """The deployment differs from ``tpch_dc16`` in its streams alone: the
    same tables on the same cluster, so the cell shares ``q18_agg_push``'s
    data and one stream's shuffle is that cell's call."""
    bench = spec.load_benchmark()
    tput = spec.resolve(bench, "q18_agg_batch4").config
    dc16 = spec.resolve(bench, "q18_agg_push").config
    assert tput["streams"] == spec.driver("streams").TENANTS == 4
    assert tput["source_scale_factor"] == 30
    for key in ("generator", "scale_factor", "rows", "l_partkey", "cluster"):
        assert tput[key] == dc16[key], key
    gen = spec.generator(tput)
    a = gen.table(dict(tput, rows=TINY), "lineitem", 2**31 + 5, 1)
    b = gen.table(dict(dc16, rows=TINY), "lineitem", 2**31 + 5, 1)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[c], b[c]) for c in a)


def _tiny_cell():
    cell = spec.resolve(spec.load_benchmark(), "q18_agg_batch4")
    cell.config["rows"] = dict(TINY)
    return cell


@pytest.fixture
def on_jax(monkeypatch):
    import repro.core.service as service
    monkeypatch.setattr(service, "default_executor", lambda: "jax")


def test_the_cell_runs_every_shuffle_batched_four_to_a_dispatch(on_jax):
    res = run.run_cell(_tiny_cell(), 2**31 + 11, 0.3, True, [], None,
                       run.CompileCounter())
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] >= 4 and res["attempted"] % 4 == 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    got = res["metrics"]
    for name in ("batch_probe_ms", "batch_stage_ms", "member_exec_ms",
                 "replay_span_ms.batch", "to_device_ms.batch",
                 "to_host_ms.batch"):
        assert got[name]["value"] > 0, name
    assert "device_idle_share.batch" not in got      # no device trace here


def test_a_shuffle_outside_a_batch_of_four_is_a_failure(on_jax, monkeypatch):
    """Where the pass forms no batch, each shuffle runs solo and correct,
    and the cell counts every one of them as failed."""
    import repro.core.service as service

    monkeypatch.setattr(service.TeShuCluster, "_batch_groups",
                        lambda self, subs: (len(subs), []))
    res = run.run_cell(_tiny_cell(), 5, 0.2, False, [], None,
                       run.CompileCounter())
    assert res["correct"] is False
    assert res["checks"]["calls_raised"]["value"] == res["attempted"] >= 4
    assert res["checks"]["values_wrong"]["value"] == 0
