"""The telemetry plane (ISSUE 7 tentpole): spans, metrics, explainability.

What the rest of the suite does not already pin:

* the tracer pair — the no-op singleton records nothing and reads no clock;
  the flight recorder nests spans through the thread-local stack, bounds its
  buffer, counts drops, and exports JSONL;
* the metrics registry — counter/gauge/histogram semantics, label cells,
  kind conflicts, collector merging, the Prometheus text format;
* one source, no drift — ``teshu_plancache_*`` and the ledger gauges are
  *read* from their canonical owners at snapshot time;
* the acceptance matrix of ``cluster.explain()`` reason codes:
  custom-combiner declines, stats-signature key mismatches, and drift
  invalidations are machine-checkable strings — and the rungs retired by
  the full-coverage lowering (``template_not_lowerable`` on built-ins,
  ``skew_rebalance_triggered``) are asserted dead;
* a ``run_pending`` pass's span tree (batch probe, batched dispatch, the
  members' shuffles) and its silence with tracing off;
* the doctor CLI (``python -m repro.launch.doctor``) over a real journal;
* the Shuffle Manager's progress/durations/stragglers views (satellite 3)
  and the versioned journal schema with tolerant migration (satellite 6).
"""
import json
import os

import numpy as np
import pytest

from conformance import (ALL_TEMPLATES, copy_bufs, make_bufs, make_topology,
                         service_for, workers_for)
from repro.core import (HASH_PART, SUM, Combiner, Msgs, ShuffleManager,
                        ShuffleRecord, TeShuCluster, TeShuService, datacenter)
from repro.core.manager import JOURNAL_VERSION
from repro.core.obs import NULL_TRACER, FlightRecorder, MetricsRegistry
from repro.core.plancache import key_diff
from repro.core.tenancy import DEFAULT_TENANT
from repro.launch import doctor

WORKERS = list(range(8))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_twice(sv, template, bufs, workers, **kw):
    sv.shuffle(template, copy_bufs(bufs), workers, workers, **kw)
    return sv.shuffle(template, copy_bufs(bufs), workers, workers, **kw)


# ---------------------------------------------------------------------------
# tracer: the no-op singleton and the flight recorder
# ---------------------------------------------------------------------------

def test_null_tracer_records_nothing(tmp_path):
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("anything", shuffle_id=1) as sp:
        sp.set(k=1)
        sp.end(extra=2)
    NULL_TRACER.point("event")
    assert NULL_TRACER.spans() == [] and len(NULL_TRACER) == 0
    assert NULL_TRACER.export_jsonl(str(tmp_path / "spans.jsonl")) == 0


def test_flight_recorder_nests_spans():
    tr = FlightRecorder()
    with tr.span("root", shuffle_id=7, tenant="t") as root:
        with tr.span("child", shuffle_id=7):
            # a manual-end span reads the *current* parent at creation
            leaf = tr.span("leaf", shuffle_id=7)
        leaf.end(rows=3)
    by_name = {s["name"]: s for s in tr.spans(7)}
    assert by_name["root"]["parent_id"] is None
    assert by_name["child"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["leaf"]["parent_id"] == by_name["child"]["span_id"]
    assert by_name["leaf"]["attrs"] == {"rows": 3}
    assert all(s["dur_s"] >= 0 for s in tr.spans())
    assert root.tenant == "t"


def test_flight_recorder_capacity_and_dropped():
    tr = FlightRecorder(capacity=4)
    for i in range(10):
        tr.point("tick", shuffle_id=i)
    assert len(tr) == 4
    assert tr.recorded_total == 10 and tr.dropped == 6
    assert [s["shuffle_id"] for s in tr.spans()] == [6, 7, 8, 9]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_flight_recorder_export_jsonl_roundtrip(tmp_path):
    tr = FlightRecorder()
    with tr.span("outer", shuffle_id=1, tenant="a", engine="jax"):
        tr.point("inner", shuffle_id=1)
    path = str(tmp_path / "spans.jsonl")
    assert tr.export_jsonl(path) == 2
    back = [json.loads(line) for line in open(path)]
    assert back == tr.spans()


def test_abandoned_and_errored_spans():
    tr = FlightRecorder()
    tr.span("never_ended")                 # abandoned: not recorded
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("exploded")
    recs = tr.spans()
    assert [s["name"] for s in recs] == ["boom"]
    assert recs[0]["attrs"]["error"] == "RuntimeError: exploded"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_negative_rejected():
    m = MetricsRegistry()
    c = m.counter("req_total", "requests")
    c.inc(tenant="a")
    c.inc(2, tenant="a")
    c.inc(tenant="b")
    assert c.get(tenant="a") == 3.0 and c.get(tenant="b") == 1.0
    assert c.get(tenant="zzz") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1, tenant="a")
    # same-name fetch returns the same family; a kind change is an error
    assert m.counter("req_total") is c
    with pytest.raises(TypeError):
        m.gauge("req_total")


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("depth")
    g.set(5, lane="x")
    g.inc(2, lane="x")
    g.dec(lane="x")
    assert g.get(lane="x") == 6.0


def test_histogram_buckets_count_sum():
    h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, tenant="a")
    cell = h.get(tenant="a")
    assert cell["count"] == 5 and cell["sum"] == pytest.approx(56.05)
    assert cell["buckets"] == {0.1: 1, 1.0: 3, 10.0: 4}   # cumulative
    assert h.get(tenant="nobody") == {"count": 0, "sum": 0.0,
                                      "buckets": {0.1: 0, 1.0: 0, 10.0: 0}}


def test_collector_merges_into_snapshot():
    m = MetricsRegistry()
    m.counter("live_total").inc(3)
    m.register_collector(lambda: [("external_gauge", {"src": "ledger"}, 42.0)])
    snap = m.snapshot()
    assert snap["live_total"] == [{"labels": {}, "value": 3.0}]
    assert snap["external_gauge"] == [{"labels": {"src": "ledger"},
                                       "value": 42.0}]
    assert m.get("external_gauge", src="ledger") == 42.0


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("c_total", "things").inc(2, tenant='a"b')
    m.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
    m.register_collector(lambda: [("coll", {}, 1.5)])
    text = m.to_prometheus()
    assert '# HELP c_total things' in text
    assert '# TYPE c_total counter' in text
    assert 'c_total{tenant="a\\"b"} 2' in text            # label escaping
    assert 'h_seconds_bucket{le="1"} 1' in text
    assert 'h_seconds_bucket{le="+Inf"} 1' in text
    assert 'h_seconds_sum 0.5' in text and 'h_seconds_count 1' in text
    assert '# TYPE coll gauge' in text and 'coll 1.5' in text


# ---------------------------------------------------------------------------
# one source, no drift: the plan cache and ledger publish via collectors
# ---------------------------------------------------------------------------

def test_plancache_metrics_agree_with_stats():
    sv = service_for("vectorized")
    bufs = make_bufs(WORKERS, "uniform", n=257)
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    stats = sv.plan_cache.stats(DEFAULT_TENANT)
    assert stats["hits"] == 1 and stats["misses"] == 1
    m = sv.obs.metrics
    assert m.get("teshu_plancache_hits", tenant=DEFAULT_TENANT) == 1.0
    assert m.get("teshu_plancache_misses", tenant=DEFAULT_TENANT) == 1.0
    assert m.get("teshu_plancache_size", tenant=DEFAULT_TENANT) \
        == stats["size"]
    # the ledger gauges read the canonical snapshot too
    assert m.get("teshu_bytes_total") == sv.stats()["total_bytes"]
    # lookup outcomes were counted on the service side as well
    assert m.get("teshu_cache_lookups_total",
                 tenant=DEFAULT_TENANT, outcome="miss") == 1.0
    assert m.get("teshu_cache_lookups_total",
                 tenant=DEFAULT_TENANT, outcome="hit") == 1.0
    assert m.get("teshu_shuffles_total", tenant=DEFAULT_TENANT,
                 template="vanilla_push", engine="vectorized") >= 1.0
    text = sv.metrics_text()
    assert "teshu_plancache_hits" in text and "teshu_bytes_total" in text


def test_key_diff_names_signature_components():
    sig_a = ("hash", "sum", 0.01, "off", 2.0, (8,), 6, None, None,
             ((0, 8), (1, 8)))
    sig_b = ("hash", "sum", 0.01, "off", 2.0, (8,), 6, None, None,
             ((0, 9), (1, 8)))
    a = ("vanilla_push", ("fp",), (0, 1), (0, 1), sig_a)
    b = ("vanilla_push", ("fp",), (0, 1), (0, 1), sig_b)
    assert key_diff(a, b) == ["signature.counts"]
    c = ("bruck",) + a[1:]
    assert key_diff(a, c) == ["template"]
    assert key_diff(a, a) == []


# ---------------------------------------------------------------------------
# the explain() acceptance matrix: machine-checkable reason codes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("template", ["bruck", "two_level"])
def test_explain_irregular_template_runs_jitted(template):
    """bruck / two_level now lower: the report shows a clean jitted replay —
    the ``template_not_lowerable`` rung is DEAD for every built-in template
    and must never be emitted (it remains reachable only for custom
    registrations outside the lowering registry)."""
    workers = WORKERS[:4] if template == "two_level" else WORKERS
    sv = service_for("jax")
    bufs = make_bufs(workers, "uniform", n=263)
    hit = _run_twice(sv, template, bufs, workers, comb_fn=SUM,
                     shuffle_id=901)
    assert hit.engine == "jax"
    assert hit.fallback_reason is None
    rep = sv.explain(901)
    assert rep.requested_executor == "jax" and rep.engine == "jax"
    assert rep.fallback_reason is None
    assert rep.fallbacks == []
    assert not any("template_not_lowerable" in line for line in rep.why())
    # no decline was counted on any rung
    m = sv.obs.metrics
    assert m.get("teshu_fallbacks_total", tenant=DEFAULT_TENANT,
                 engine="jax", reason="template_not_lowerable") == 0.0


def test_explain_custom_combiner_decline():
    """A combiner outside the jnp registry cannot run inside the jitted
    program; the vectorized plane still executes it."""
    first = Combiner("first", lambda a, b: a, np.minimum,
                     order_sensitive=True)
    sv = service_for("jax")
    bufs = make_bufs(WORKERS, "uniform", n=269)
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=first,
                     shuffle_id=902)
    assert hit.engine == "vectorized"
    assert hit.fallback_reason == "unsupported_combiner"
    rep = sv.explain(902)
    assert rep.fallbacks == [{"engine": "jax",
                              "reason": "unsupported_combiner"}]
    assert rep.engine == "vectorized"


def test_explain_skew_triggered_runs_jitted():
    """A triggered rebalance rewrites PART into hot-key scatter — the jax
    lowering now freezes the split tables into the trace: explain reports a
    clean jitted replay (the ``skew_rebalance_triggered`` reason code is
    dead and must never be emitted), while still naming the skew verdict."""
    topo = datacenter(4, 2, 1)
    sv = TeShuService(topo, executor="jax")
    bufs = make_bufs(WORKERS, "zipf", n=8000, key_space=500, width=1)
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM,
                     balance="auto", shuffle_id=903)
    rebalance = dict(hit.decisions).get("rebalance")
    assert rebalance is not None and rebalance.triggered  # else vacuous
    assert hit.engine == "jax"
    assert hit.fallback_reason is None
    rep = sv.explain(903)
    assert rep.engine == "jax" and rep.fallback_reason is None
    assert rep.fallbacks == []
    assert rep.skew is not None and rep.skew["triggered"]
    assert rep.skew["splits"] == len(rebalance.splits)
    assert not any("skew_rebalance_triggered" in line for line in rep.why())


def test_explain_stats_signature_miss():
    """A workload whose per-worker counts leave their log2 bucket misses with
    a key-component diff naming exactly the diverged signature part."""
    sv = service_for("vectorized")
    small = make_bufs(WORKERS, "uniform", n=300)
    big = make_bufs(WORKERS, "uniform", n=1200)       # new log2 count bucket
    sv.shuffle("vanilla_push", copy_bufs(small), WORKERS, WORKERS,
               comb_fn=SUM, shuffle_id=904)
    res = sv.shuffle("vanilla_push", copy_bufs(big), WORKERS, WORKERS,
                     comb_fn=SUM, shuffle_id=905)
    assert not res.cached
    rep = sv.explain(905)
    assert rep.cache["outcome"] == "miss"
    assert rep.cache["reason"] == "key_mismatch"
    assert "signature.counts" in rep.cache["diff"]
    assert any("signature.counts" in line for line in rep.why())
    # and the first call's report shows the cold miss
    assert sv.explain(904).cache["reason"] == "cold"


def test_explain_drift_invalidation():
    """Same signature, different distribution: the cached run's observed
    reduction drifts, the plan is dropped, and both the drifted run's report
    and the next lookup carry the invalidation."""
    topo = datacenter(2, 2, 2, oversubscription=10.0,
                      combine_bytes_per_s=64e9)
    nw = topo.num_workers
    sv = TeShuService(topo)
    workers = list(range(nw))
    rng = np.random.default_rng(3)
    base = rng.integers(0, 65536, 100)
    base[0] = 65535
    dup = {w: Msgs(np.repeat(rng.permutation(base), 40),
                   rng.random((4000, 1))) for w in workers}
    per = 65536 // nw
    uniq = {}
    for w in workers:
        keys = w * per + rng.choice(per, size=4000, replace=False)
        keys[0] = 65535
        uniq[w] = Msgs(keys, rng.random((4000, 1)))
    sv.shuffle("network_aware", copy_bufs(dup), workers, workers,
               comb_fn=SUM, rate=0.05, shuffle_id=906)
    drifted = sv.shuffle("network_aware", copy_bufs(uniq), workers, workers,
                         comb_fn=SUM, rate=0.05, shuffle_id=907)
    assert drifted.cached                             # keyed the same -> hit
    assert sv.cache_stats()["invalidations"] == 1     # ...but drift detected
    rep = sv.explain(907)
    assert rep.drift is not None and rep.drift["kind"] == "reduction"
    assert any("drift-invalidated" in line for line in rep.why())
    assert sv.obs.metrics.get("teshu_drift_invalidations_total",
                              tenant=DEFAULT_TENANT, kind="reduction") == 1.0
    # the next run's lookup explains the invalidation as its miss reason
    sv.shuffle("network_aware", copy_bufs(uniq), workers, workers,
               comb_fn=SUM, rate=0.05, shuffle_id=908)
    assert sv.explain(908).cache["reason"] == "invalidated_reduction_drift"


def test_explain_unknown_shuffle():
    sv = service_for("vectorized")
    rep = sv.explain(31337)
    assert rep.why() == ["no recorded decisions for this shuffle id"]


# ---------------------------------------------------------------------------
# span plumbing through the service
# ---------------------------------------------------------------------------

def test_tracing_off_records_zero_spans():
    sv = service_for("vectorized")
    bufs = make_bufs(WORKERS, "uniform", n=271)
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    assert sv.spans() == []
    assert not sv.obs.tracer.enabled


def test_tracing_on_builds_span_tree(tmp_path):
    sv = service_for("vectorized", tracing=True)
    bufs = make_bufs(WORKERS, "uniform", n=277)
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM,
               shuffle_id=910)
    # second call was a vectorized cache hit: root + lookup + exec spans
    spans = sv.spans(910)
    by_name = {s["name"]: s for s in spans}
    assert {"shuffle", "plan_lookup", "exec"} <= set(by_name)
    root = by_name["shuffle"]
    assert root["parent_id"] is None
    assert by_name["plan_lookup"]["parent_id"] == root["span_id"]
    assert by_name["exec"]["parent_id"] == root["span_id"]
    assert by_name["exec"]["attrs"]["engine"] == "vectorized"
    assert root["attrs"]["engine"] == "vectorized"
    assert root["attrs"]["cache"] == "hit"
    assert root["tenant"] == DEFAULT_TENANT
    # explain() attaches the same spans; export round-trips them
    assert sv.explain(910).spans == spans
    path = str(tmp_path / "spans.jsonl")
    assert sv.export_spans(path) == len(sv.spans())
    # toggling off stops recording without clearing history
    sv.disable_tracing()
    n = len(sv.spans())
    sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS, comb_fn=SUM)
    assert len(sv.spans()) == n


def test_tracing_jax_spans_lower_and_replay():
    sv = service_for("jax", tracing=True)
    # a row count and width no other test of this file replays, so the
    # first replay here is the program's first trace
    bufs = make_bufs(WORKERS, "uniform", n=281, width=3)
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM,
                     shuffle_id=911)
    assert hit.engine == "jax"
    by_name = {s["name"]: s for s in sv.spans(911)}
    assert by_name["exec"]["attrs"]["engine"] == "jax"
    assert by_name["lower"]["attrs"]["declined"] is False
    assert by_name["jit_replay"]["attrs"]["rows"] == 8 * 281
    assert by_name["jit_replay"]["attrs"]["compiled"] is True
    # steady-state replay: this program's trace cache did not grow
    sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
               comb_fn=SUM, shuffle_id=911)
    jit = [s for s in sv.spans(911) if s["name"] == "jit_replay"]
    assert [s["attrs"]["compiled"] for s in jit] == [True, False]


EXEC_STAGES = ("stage_inputs", "to_device", "jit_replay", "to_host",
               "ledger_replay", "split_outputs")


def _skewed_case():
    """A Zipf workload whose plan splits hot keys (the owner-merge runs)."""
    topo = datacenter(4, 2, 1)
    bufs = make_bufs(WORKERS, "zipf", n=8000, key_space=500, width=1)
    return topo, bufs, {"comb_fn": SUM, "balance": "auto"}


def _children(spans, parent):
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def _within(child, parent):
    return parent["t0"] <= child["t0"] <= child["t1"] <= parent["t1"]


def test_jax_exec_splits_into_stage_spans():
    sv = service_for("jax", tracing=True)
    bufs = make_bufs(WORKERS, "uniform", n=331)
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM,
                     shuffle_id=912)
    assert hit.engine == "jax"
    spans = sv.spans(912)
    (root,) = [s for s in spans if s["name"] == "shuffle"
               and s["attrs"]["engine"] == "jax"]
    kids = {s["name"]: s for s in _children(spans, root)}
    assert {"plan_key", "plan_lookup", "exec"} <= set(kids)
    assert all(_within(s, root) for s in kids.values())
    # the stats signature pass runs inside the root span, before the lookup
    assert kids["plan_key"]["t1"] <= kids["plan_lookup"]["t0"]
    exe = kids["exec"]
    stages = _children(spans, exe)
    assert [s["name"] for s in sorted(stages, key=lambda s: s["t0"])] == \
        list(EXEC_STAGES)
    assert all(_within(s, exe) for s in stages)
    assert not any(s["name"] == "owner_merge" for s in spans)


def test_skewed_jax_exec_has_an_owner_merge_span():
    topo, bufs, kw = _skewed_case()
    sv = TeShuService(topo, executor="jax", tracing=True)
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, shuffle_id=913, **kw)
    assert hit.engine == "jax"
    assert dict(hit.decisions)["rebalance"].triggered
    spans = sv.spans(913)
    exe = [s for s in spans if s["name"] == "exec"][-1]
    stages = sorted(_children(spans, exe), key=lambda s: s["t0"])
    assert [s["name"] for s in stages] == list(EXEC_STAGES) + ["owner_merge"]
    assert all(_within(s, exe) for s in stages)


@pytest.mark.parametrize("case", ALL_TEMPLATES + ("skewed",))
def test_jax_outputs_identical_with_tracing_on_and_off(case):
    if case == "skewed":
        topo, bufs, kw = _skewed_case()
        template, workers = "vanilla_push", WORKERS
    else:
        template, workers = case, workers_for(case)
        topo, bufs, kw = None, make_bufs(workers, "uniform", n=337), {
            "comb_fn": SUM}
    out = {}
    for tracing in (False, True):
        sv = service_for("jax", topo, tracing=tracing)
        res = _run_twice(sv, template, bufs, workers, **kw)
        assert res.engine == "jax"
        assert bool(sv.spans()) == tracing
        out[tracing] = res
    off, on = out[False], out[True]
    assert off.bufs.keys() == on.bufs.keys()
    for d in off.bufs:
        assert np.array_equal(off.bufs[d].keys, on.bufs[d].keys)
        assert np.array_equal(off.bufs[d].vals, on.bufs[d].vals)
    for lane in ("total_bytes", "bytes_per_level", "recv_bytes_per_worker",
                 "bytes_per_tenant"):
        assert off.stats[lane] == on.stats[lane]


def test_tracing_off_builds_no_annotation_and_adds_no_transfer(monkeypatch):
    import jax

    counts = {"annotation": 0, "device_put": 0, "block_until_ready": 0}

    class CountedAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            counts["annotation"] += 1
            super().__init__(*a, **kw)

    def counted(name, fn):
        def wrapper(*a, **kw):
            counts[name] += 1
            return fn(*a, **kw)
        return wrapper
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountedAnnotation)
    monkeypatch.setattr(jax, "device_put", counted("device_put",
                                                   jax.device_put))
    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block_until_ready", jax.block_until_ready))
    sv = service_for("jax")
    bufs = make_bufs(WORKERS, "uniform", n=347)
    assert _run_twice(sv, "vanilla_push", bufs, WORKERS,
                      comb_fn=SUM).engine == "jax"
    assert sv.spans() == []
    assert counts == {"annotation": 0, "device_put": 0,
                      "block_until_ready": 0}
    # the same replay traced: it puts nothing on the device itself, and
    # waits once a call, for the program's outputs
    sv.enable_tracing()
    for _ in range(2):
        sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                   comb_fn=SUM)
    assert counts["annotation"] == len(sv.spans())
    assert counts["device_put"] == 0
    assert counts["block_until_ready"] == 2
    # the traced calls take the untraced calls' operands, so they hit the
    # program those compiled: no retrace
    jit = [s for s in sv.spans() if s["name"] == "jit_replay"]
    assert [s["attrs"]["compiled"] for s in jit] == [False, False]


def test_traced_call_reuses_the_untraced_program():
    """After untraced warm-up, a traced call of the same shape runs the
    program the untraced calls compiled: the replay cache does not grow
    and ``jit_replay`` says nothing compiled."""
    from repro.core import replay_cache_size

    sv = service_for("jax")
    bufs = make_bufs(WORKERS, "uniform", n=367)     # a shape of its own
    assert _run_twice(sv, "vanilla_push", bufs, WORKERS,
                      comb_fn=SUM).engine == "jax"
    before = replay_cache_size()
    sv.enable_tracing()
    res = sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                     comb_fn=SUM, shuffle_id=918)
    assert res.engine == "jax"
    assert replay_cache_size() == before
    (jit,) = [s for s in sv.spans(918) if s["name"] == "jit_replay"]
    assert jit["attrs"]["compiled"] is False


def test_batched_dispatch_records_its_device_stages():
    """``run_pending``'s one vmapped dispatch records to_device, jit_replay
    and to_host with the member count; its members' execs have no device
    stage of their own."""
    cl = TeShuCluster(make_topology(), execution="auto", executor="jax",
                      tracing=True)
    tenants = [cl.tenant(f"t{i}") for i in range(3)]
    bufs = make_bufs(WORKERS, "uniform", n=349)
    for t in tenants:                               # plan + trace per tenant
        _run_twice(t, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    cl.obs.tracer.clear()
    tickets = [t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                        comb_fn=SUM) for t in tenants]
    results = cl.run_pending()
    assert all(results[tk].batched for tk in tickets)
    spans = cl.spans()
    device = [s for s in spans if s["name"] in ("to_device", "jit_replay",
                                                "to_host")]
    assert [s["name"] for s in device] == ["to_device", "jit_replay",
                                           "to_host"]
    assert all(s["attrs"]["batch"] == 3 for s in device)
    assert device[1]["attrs"]["rows"] == 3 * 8 * 349
    assert [s["name"] for s in spans if s["name"] == "exec"] == ["exec"] * 3
    assert sum(s["name"] == "ledger_replay" for s in spans) == 3


def _four_stream_pass(tracing):
    """Four tenants with their plans and the batched program warm, then one
    traced-or-not ``run_pending`` pass of one submission each."""
    cl = TeShuCluster(make_topology(), execution="auto", executor="jax",
                      tracing=tracing)
    tenants = [cl.tenant(f"s{i}") for i in range(4)]
    bufs = make_bufs(WORKERS, "uniform", n=359)
    for _ in range(2):                      # plans, then the batched program
        for t in tenants:
            t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                     comb_fn=SUM)
        cl.run_pending()
    cl.obs.tracer.clear()
    tickets = [t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                        comb_fn=SUM) for t in tenants]
    results = cl.run_pending()
    assert all(results[tk].batched for tk in tickets)
    return cl, [results[tk] for tk in tickets]


def test_batched_pass_identical_with_tracing_on_and_off():
    """A batched ``run_pending`` pass gives every member the same outputs,
    bit for bit, and the same ledger lanes whether it is traced or not."""
    (_, off), (_, on) = _four_stream_pass(False), _four_stream_pass(True)
    for a, b in zip(off, on):
        assert a.engine == b.engine == "jax"
        assert a.bufs.keys() == b.bufs.keys()
        for d in a.bufs:
            assert np.array_equal(a.bufs[d].keys, b.bufs[d].keys)
            assert np.array_equal(a.bufs[d].vals, b.bufs[d].vals)
        for lane in ("total_bytes", "bytes_per_level",
                     "recv_bytes_per_worker", "bytes_per_tenant"):
            assert a.stats[lane] == b.stats[lane]


def test_run_pending_pass_is_a_span_tree():
    """A pass is rooted at ``run_pending``: the batch probe, the one batched
    dispatch (its host staging and device stages) and the members' shuffles
    nest under it, and each member's ``exec`` says it replayed a slice."""
    cl, _ = _four_stream_pass(tracing=True)
    spans = cl.spans()
    (root,) = [s for s in spans if s["name"] == "run_pending"]
    assert root["parent_id"] is None
    assert root["attrs"] == {"submissions": 4, "batches": 1}
    probe, dispatch = sorted(
        (s for s in spans if s["name"] in ("batch_probe", "batch_dispatch")),
        key=lambda s: s["t0"])
    assert probe["name"] == "batch_probe" and dispatch["name"] == "batch_dispatch"
    assert probe["parent_id"] == dispatch["parent_id"] == root["span_id"]
    assert probe["attrs"] == {"candidates": 4, "grouped": 4}
    assert dispatch["attrs"] == {"members": 4, "rows": 4 * 8 * 359}
    assert probe["t1"] <= dispatch["t0"]
    stages = sorted(_children(spans, dispatch), key=lambda s: s["t0"])
    assert [s["name"] for s in stages] == ["stage_batch", "to_device",
                                           "jit_replay", "to_host"]
    assert all(_within(s, dispatch) for s in stages)
    assert stages[2]["attrs"]["batch"] == 4
    shuffles = [s for s in spans if s["name"] == "shuffle"]
    assert len(shuffles) == 4
    assert all(s["parent_id"] == root["span_id"] and _within(s, root)
               and s["t0"] >= dispatch["t1"] for s in shuffles)
    execs = [s for s in spans if s["name"] == "exec"]
    assert [s["attrs"]["batched"] for s in execs] == [True] * 4
    for exe in execs:                   # no device stage inside a member
        assert {s["name"] for s in _children(spans, exe)} == {
            "stage_inputs", "ledger_replay", "split_outputs"}
    # a replay of its own says so
    t = cl.tenant("s0")
    t.shuffle("vanilla_push", make_bufs(WORKERS, "uniform", n=359), WORKERS,
              WORKERS, comb_fn=SUM, shuffle_id=917)
    (solo,) = [s for s in cl.spans(917) if s["name"] == "exec"]
    assert solo["attrs"]["batched"] is False


def test_tracing_off_pass_builds_no_annotation_and_adds_no_transfer(
        monkeypatch):
    """With tracing off a batched pass opens no span, builds no profiler
    annotation, and puts or waits on nothing beyond the program's call."""
    import jax

    counts = {"annotation": 0, "device_put": 0, "block_until_ready": 0}

    class CountedAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            counts["annotation"] += 1
            super().__init__(*a, **kw)

    def counted(name, fn):
        def wrapper(*a, **kw):
            counts[name] += 1
            return fn(*a, **kw)
        return wrapper
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountedAnnotation)
    monkeypatch.setattr(jax, "device_put", counted("device_put",
                                                   jax.device_put))
    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block_until_ready", jax.block_until_ready))
    cl, _ = _four_stream_pass(tracing=False)
    assert cl.spans() == []
    assert counts == {"annotation": 0, "device_put": 0,
                      "block_until_ready": 0}


def _profiled(tmp_path, fn):
    """Host events of a CPU profiler trace taken around ``fn()``."""
    import glob

    import jax

    from chipbench import trace

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    return trace.read_xspace(path)[1]


def test_spans_are_mirrored_into_the_profiler_trace(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    sv = service_for("jax", tracing=True)
    bufs = make_bufs(WORKERS, "uniform", n=353)
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    host = _profiled(tmp_path, lambda: sv.shuffle(
        "vanilla_push", copy_bufs(bufs), WORKERS, WORKERS, comb_fn=SUM,
        shuffle_id=914))
    events = {}
    for name, t0, t1 in host:
        if name.startswith("teshu."):
            events.setdefault(name, []).append((t0, t1))
    recorded = {"teshu." + s["name"] for s in sv.spans(914)}
    assert {"teshu.shuffle", "teshu.exec", "teshu.jit_replay"} <= recorded
    assert set(events) == recorded
    (sh,), (ex,), (jr,) = (events[f"teshu.{n}"]
                           for n in ("shuffle", "exec", "jit_replay"))
    assert sh[0] <= ex[0] <= jr[0] <= jr[1] <= ex[1] <= sh[1]
    # the same intervals as the recorder's spans, on the profiler's clock
    spans = {s["name"]: s for s in sv.spans(914)}
    for name, (t0, t1) in (("shuffle", sh), ("exec", ex), ("jit_replay", jr)):
        dur = spans[name]["t1"] - spans[name]["t0"]
        assert (t1 - t0) * 1e-9 == pytest.approx(dur, rel=0.05, abs=2e-4)


def test_mirrored_spans_ended_out_of_order(tmp_path, monkeypatch):
    """Manual ``end()`` need not follow the order spans were opened in: each
    annotation keeps its own interval."""
    import time

    monkeypatch.syspath_prepend(ROOT)
    import jax  # noqa: F401  (the recorder mirrors only where jax is loaded)

    tr = FlightRecorder()

    def overlapping():
        a = tr.span("outer_first")
        time.sleep(0.01)
        b = tr.span("inner_last")
        time.sleep(0.01)
        a.end()
        time.sleep(0.01)
        b.end()
        tr.point("instant")
    host = _profiled(tmp_path, overlapping)
    got = {n: (t0, t1) for n, t0, t1 in host if n.startswith("teshu.")}
    assert set(got) == {"teshu.outer_first", "teshu.inner_last"}
    (a0, a1), (b0, b1) = got["teshu.outer_first"], got["teshu.inner_last"]
    assert a0 < b0 < a1 < b1
    spans = {s["name"]: s for s in tr.spans()}
    for name, (t0, t1) in (("outer_first", (a0, a1)),
                           ("inner_last", (b0, b1))):
        dur = spans[name]["t1"] - spans[name]["t0"]
        assert (t1 - t0) * 1e-9 == pytest.approx(dur, rel=0.05, abs=2e-4)


def test_streaming_metrics_and_spans():
    sv = service_for("vectorized", tracing=True)
    sess = sv.open_stream("vanilla_push", WORKERS, WORKERS, comb_fn=SUM,
                          max_inflight=2)
    bufs = make_bufs(WORKERS, "uniform", n=400)
    fed = sess.feed(copy_bufs(bufs))
    assert fed > 0
    out = sess.drain()
    assert set(out["bufs"]) == set(WORKERS)
    m = sv.obs.metrics
    assert m.get("teshu_stream_chunks_total", tenant=DEFAULT_TENANT) == fed
    if sess.backpressure_stalls:
        assert m.get("teshu_stream_backpressure_stalls_total",
                     tenant=DEFAULT_TENANT) == sess.backpressure_stalls
    names = {s["name"] for s in sv.spans(sess.shuffle_id)}
    assert {"stream_feed", "stream_drain"} <= names


def test_admission_wait_histogram():
    sv = TeShuCluster(make_topology())
    a = sv.tenant("a")
    bufs = make_bufs(WORKERS, "uniform", n=283)
    t1 = a.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                  comb_fn=SUM)
    t2 = a.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                  comb_fn=SUM)
    results = sv.run_pending()
    assert not isinstance(results[t1], Exception)
    assert not isinstance(results[t2], Exception)
    cell = sv.obs.metrics.histogram("teshu_admission_wait_seconds").get(
        tenant="a")
    assert cell["count"] == 2 and cell["sum"] >= 0.0


def test_recovery_metrics_and_report():
    sv = TeShuService(make_topology(), resilience="recover", tracing=True)
    rng = np.random.default_rng(3)
    base = rng.integers(0, 4096, 40)
    bufs = {w: Msgs(np.repeat(rng.permutation(base), 10),
                    rng.random((400, 1))) for w in WORKERS}
    sv.shuffle("network_aware", copy_bufs(bufs), WORKERS, WORKERS,
               comb_fn=SUM, rate=0.05)
    sv.inject_fault(3, after_stage=0)
    rec = sv.shuffle("network_aware", copy_bufs(bufs), WORKERS, WORKERS,
                     comb_fn=SUM, rate=0.05, shuffle_id=912)
    assert rec.attempts == 2
    m = sv.obs.metrics
    assert m.get("teshu_recovery_attempts_total",
                 tenant=DEFAULT_TENANT) == 1.0
    hist = m.histogram("teshu_recovery_restart_workers").get(
        tenant=DEFAULT_TENANT)
    assert hist["count"] == 1 and hist["sum"] >= 1
    rep = sv.explain(912)
    assert rep.status == "ok" and rep.attempts == 2
    assert rep.failures and rep.failures[0]["info"]["dead"] == [3]
    assert rep.recovery
    assert any("recovered after 2 attempts" in line for line in rep.why())
    points = [s for s in sv.spans(912) if s["name"] == "recovery"]
    assert len(points) == 1 and points[0]["attrs"]["restarted"] == [3]


# ---------------------------------------------------------------------------
# satellite 3: manager progress / durations / stragglers
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def test_manager_views_empty_journal():
    mgr = ShuffleManager()
    assert mgr.progress(1) == {"started": [], "finished": [], "pending": []}
    assert mgr.durations(1) == {}
    assert mgr.stragglers(1) == []
    assert mgr.incomplete_shuffles() == []


def test_manager_views_multi_attempt():
    clk = _Clock()
    mgr = ShuffleManager(clock=clk)
    for attempt in (0, 1):
        for w in (0, 1):
            clk.t = 10.0 * attempt + w
            mgr.record_start(w, 5, "vanilla_push", attempt=attempt)
        clk.t = 10.0 * attempt + 5.0
        mgr.record_end(0, 5, "vanilla_push", attempt=attempt)
    # worker 1 never finished either attempt
    assert mgr.progress(5) == {"started": [0, 1], "finished": [0],
                               "pending": [1]}
    # durations use the latest start/end per worker (attempt 1 overwrites 0)
    assert mgr.durations(5) == {0: pytest.approx(5.0)}
    assert len(mgr.records(5)) == 6


def test_manager_views_tenant_filtered():
    clk = _Clock()
    mgr = ShuffleManager(clock=clk)
    mgr.record_start(0, 1, "vanilla_push", tenant="alpha")
    mgr.record_end(0, 1, "vanilla_push", tenant="alpha")
    mgr.record_start(1, 2, "bruck", tenant="beta")
    assert [r.shuffle_id for r in mgr.records(tenant="alpha")] == [1, 1]
    assert [r.shuffle_id for r in mgr.records(tenant="beta")] == [2]
    assert mgr.records(tenant="nobody") == []
    assert mgr.tenants() == ["alpha", "beta"]


def test_stragglers_factor_boundary():
    """Duration exactly factor x median is NOT a straggler (strict >);
    epsilon above is; a pending worker is flagged once its elapsed time
    crosses the same threshold."""
    clk = _Clock()
    mgr = ShuffleManager(clock=clk)
    # three finished workers: durations 1.0, 1.0, 3.0 -> median 1.0
    for w, dur in ((0, 1.0), (1, 1.0), (2, 3.0)):
        clk.t = 0.0
        mgr.record_start(w, 9, "vanilla_push")
        clk.t = dur
        mgr.record_end(w, 9, "vanilla_push")
    assert mgr.stragglers(9, factor=3.0) == []            # 3.0 == 3 x 1.0
    assert mgr.stragglers(9, factor=2.9) == [2]
    # a started-but-unfinished worker: flagged only past the threshold
    clk.t = 0.0
    mgr.record_start(7, 9, "vanilla_push")
    assert mgr.stragglers(9, factor=3.0, now=3.0) == []
    assert mgr.stragglers(9, factor=3.0, now=3.1) == [7]
    # now defaults to the injected clock
    clk.t = 4.0
    assert mgr.stragglers(9, factor=3.0) == [7]


# ---------------------------------------------------------------------------
# satellite 6: versioned journal schema + tolerant migration
# ---------------------------------------------------------------------------

def test_journal_lines_carry_version():
    rec = ShuffleRecord(0, 1, "vanilla_push", "start", 1.0)
    d = json.loads(rec.to_json())
    assert d["v"] == JOURNAL_VERSION >= 2
    assert "version" not in d                      # compact wire name only
    back = ShuffleRecord.from_json(rec.to_json())
    assert back.version == JOURNAL_VERSION
    # seed-format compatibility is untouched by the version stamp
    assert "tenant" not in d and "attempt" not in d


def test_journal_reader_is_version_tolerant():
    # pre-version line: replays as schema v0
    old = ShuffleRecord.from_json(
        '{"wid": 0, "shuffle_id": 1, "template_id": "x", '
        '"kind": "start", "ts": 1.0}')
    assert old.version == 0 and old.tenant == DEFAULT_TENANT
    # future line: unknown fields dropped, version preserved
    new = ShuffleRecord.from_json(
        '{"wid": 0, "shuffle_id": 1, "template_id": "x", "kind": "end", '
        '"ts": 2.0, "v": 9, "hologram": true}')
    assert new.version == 9 and not hasattr(new, "hologram")


def test_pre_version_journal_migrates(tmp_path):
    fixture = os.path.join(FIXTURES, "pre_version_journal.jsonl")
    mgr = ShuffleManager.recover(fixture)
    recs = mgr.records()
    assert len(recs) == 7
    versions = {r.version for r in recs}
    assert versions == {0, 1, 2}                  # seed, current, future
    assert mgr.progress(1) == {"started": [0, 1], "finished": [0, 1],
                               "pending": []}
    # re-journaling replayed records preserves their provenance version;
    # records created fresh by this code stamp the current schema
    out = tmp_path / "rewritten.jsonl"
    with open(out, "w") as f:
        for r in recs:
            f.write(r.to_json() + "\n")
    assert [json.loads(line)["v"] for line in open(out)] \
        == [r.version for r in recs]


# ---------------------------------------------------------------------------
# the doctor CLI
# ---------------------------------------------------------------------------

def test_doctor_on_live_journal(tmp_path, capsys):
    journal = str(tmp_path / "journal.jsonl")
    sv = TeShuService(make_topology(), journal_path=journal,
                      resilience="recover")
    bufs = make_bufs(WORKERS, "uniform", n=293)
    sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS, comb_fn=SUM)
    sv.inject_fault(3, after_stage=-1)
    rec = sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                     comb_fn=SUM)
    assert rec.attempts == 2

    reports = doctor.diagnose(journal)
    assert [r["shuffle_id"] for r in reports] == [1, 2]
    assert reports[0]["status"] == "ok" and reports[0]["attempts"] == 1
    assert reports[1]["status"] == "recovered"
    assert reports[1]["attempts"] == 2
    assert reports[1]["failures"][0]["dead"] == [3]
    assert reports[1]["journal_versions"] == [JOURNAL_VERSION]
    assert reports[1]["workers"]["pending"] == []

    # text rendering and exit codes through main()
    assert doctor.main([journal]) == 0
    out = capsys.readouterr().out
    assert "shuffle 2 [vanilla_push]" in out and "RECOVERED" in out
    assert doctor.main([journal, "--shuffle", "2", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert len(payload) == 1 and payload[0]["shuffle_id"] == 2
    # no matching records -> exit 1
    assert doctor.main([journal, "--tenant", "nobody"]) == 1


def test_doctor_flags_incomplete_shuffle(tmp_path):
    journal = tmp_path / "stuck.jsonl"
    lines = [
        {"wid": 0, "shuffle_id": 4, "template_id": "bruck", "kind": "start",
         "ts": 1.0, "v": 1},
        {"wid": 1, "shuffle_id": 4, "template_id": "bruck", "kind": "start",
         "ts": 1.0, "v": 1},
        {"wid": 0, "shuffle_id": 4, "template_id": "bruck", "kind": "end",
         "ts": 1.5, "v": 1},
    ]
    journal.write_text("\n".join(json.dumps(d) for d in lines) + "\n")
    reports = doctor.diagnose(str(journal), straggler_factor=2.0)
    assert len(reports) == 1
    rep = reports[0]
    assert rep["status"] == "incomplete"
    assert rep["workers"]["pending"] == [1]
