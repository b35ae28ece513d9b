"""The jitted replay executor (ISSUE 6 tentpole).

What the conformance matrix (test_conformance.py) does not already pin:

* compilation economics — the whole replay is ONE rolled ``lax.scan``
  program, so repeated hits, and even *different plans* with the same shape
  signature, reuse a single trace (``replay_cache_size`` deltas);
* full template coverage — the irregular bruck / two_level routes and
  triggered skew rebalances now replay jitted (no decline), byte-identical
  to the threaded reference;
* the decline ladder — streaming, fault state, custom templates, and exotic
  partFuncs still fall back (jax -> vectorized -> threaded) with correct
  engine markers and no behavior change;
* trace-cache economics — the LRU bound (``set_replay_cache_limit``) evicts
  oldest programs and counts ``trace_evictions``;
* batched multi-tenant dispatch — same-signature wfair submissions execute
  as one vmapped program with per-tenant ledger lanes identical to serial;
  four tenants' streams of submit + ``run_pending`` passes deliver each
  member's own numpy group-by bit for bit (sum, min, max; members whose
  longest segments differ a thousandfold), each member handed its slice;
* the executor knob stack — per-call > per-tenant > cluster resolution;
* plan-lifetime lowering reuse (``plancache.attach_lowering``);
* the COMB fold in rounds by rank against the row-serial ``lax.scan`` fold
  it replaced, byte for byte, alone and under ``vmap``, and the
  ``fold_rounds`` it reports;
* the wire format of the replay's 64-bit keys and payloads (uint32 words,
  as their bits, or on a TPU as float32 pairs): edge bit patterns arrive
  unchanged, the pairs hold what a TPU holds, every template replays
  through either form, and the transfers count the words.
"""
import math

import numpy as np
import pytest

from conformance import (ALL_TEMPLATES, assert_identical, conformance_case,
                         copy_bufs, make_bufs, make_topology, service_for,
                         workers_for)
from repro.core import (COMBINERS, SUM, Msgs, PartFn, TeShuCluster,
                        TeShuService, datacenter)
from repro.core import jaxplan
from repro.core.jaxplan import (_combine, lower_plan, plan_decline,
                                replay_cache_limit, replay_cache_size,
                                set_replay_cache_limit, trace_evictions,
                                try_run_jax)
from repro.core.plancache import get_lowering

WORKERS = list(range(8))


def _jax_service(**kw):
    return service_for("jax", **kw)


def _run_twice(sv, template, bufs, workers, **kw):
    sv.shuffle(template, copy_bufs(bufs), workers, workers, **kw)
    return sv.shuffle(template, copy_bufs(bufs), workers, workers, **kw)


# ---------------------------------------------------------------------------
# compilation: one rolled program
# ---------------------------------------------------------------------------

def test_one_trace_per_plan_shape():
    """A plan replays through exactly one compiled program: the first hit
    traces once, every later hit — and even a different service's plan with
    the same spec/shape — reuses it."""
    bufs = make_bufs(WORKERS, "uniform", n=311)       # shape unique to this test
    sv = _jax_service()
    sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS, comb_fn=SUM)
    before = replay_cache_size()
    r1 = sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                    comb_fn=SUM)
    assert r1.engine == "jax"
    assert replay_cache_size() == before + 1          # the one trace
    for _ in range(3):
        r = sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                       comb_fn=SUM)
        assert r.engine == "jax"
    assert replay_cache_size() == before + 1          # no retrace on replays
    sv2 = _jax_service()                              # fresh service, new plan
    r2 = _run_twice(sv2, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    assert r2.engine == "jax"
    assert replay_cache_size() == before + 1          # same spec+shape: reused


def test_distinct_spec_is_a_new_trace():
    """Changing the static half (template) compiles one more program."""
    bufs = make_bufs(WORKERS, "uniform", n=313)
    sv = _jax_service()
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    before = replay_cache_size()
    r = _run_twice(sv, "coordinated", bufs, WORKERS, comb_fn=SUM)
    assert r.engine == "jax"
    assert replay_cache_size() == before + 1


def test_trace_cache_is_a_bounded_lru():
    """``replay_cache_limit`` bounds the program cache: pushing more distinct
    shapes than the limit evicts the oldest traces and counts them in
    ``trace_evictions`` (surfaced as ``teshu_jit_trace_evictions``)."""
    sv = _jax_service()
    prev = set_replay_cache_limit(4)
    try:
        assert replay_cache_limit() == 4
        ev0 = trace_evictions()
        for i in range(6):                      # 6 distinct shapes > limit 4
            bufs = make_bufs(WORKERS, "uniform", n=401 + i)
            r = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
            assert r.engine == "jax"
        assert replay_cache_size() <= 4
        assert trace_evictions() > ev0
        # a replayed shape still hits after evictions settle
        bufs = make_bufs(WORKERS, "uniform", n=406)
        assert sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                          comb_fn=SUM).engine == "jax"
    finally:
        set_replay_cache_limit(prev)


# ---------------------------------------------------------------------------
# the decline ladder
# ---------------------------------------------------------------------------

def test_streaming_replay_falls_back_to_vectorized():
    """A streamed plan replay is chunk-pipelined state the lowering does not
    encode: the jax executor declines and the vectorized streamed replay
    runs instead — byte-identical to a barrier reference."""
    bufs = make_bufs(WORKERS, "uniform")
    sv = TeShuService(make_topology(), executor="jax", streaming="auto")
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    assert hit.cached and hit.streamed
    assert hit.engine == "vectorized"
    ref = _run_twice(service_for("threaded"), "vanilla_push", bufs, WORKERS,
                     comb_fn=SUM)
    assert_identical(hit.bufs, ref.bufs)


def test_triggered_skew_replays_jitted():
    """A triggered rebalance rewrites PART into positional hot-key scatter —
    the lowering freezes the split tables into the traced program and replays
    jitted, byte-identical to the threaded reference."""
    bufs = make_bufs(WORKERS, "zipf", n=8000, key_space=500, width=1)

    def run(executor):
        sv = service_for(executor, topo=datacenter(4, 2, 1))
        sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                   comb_fn=SUM, balance="auto")
        return sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                          comb_fn=SUM, balance="auto")

    hit = run("jax")
    rebalance = dict(hit.decisions).get("rebalance")
    assert rebalance is not None and rebalance.triggered  # else vacuous
    assert hit.cached and hit.engine == "jax"
    assert hit.fallback_reason is None
    assert_identical(hit.bufs, run("threaded").bufs)


def test_fault_state_falls_back_to_threaded():
    """Any injected fault/straggler state needs the thread-level simulation:
    both replay planes decline, the threaded executor still replays the plan."""
    bufs = make_bufs(WORKERS, "uniform")
    sv = _jax_service()
    ref = _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    assert ref.engine == "jax"
    sv.delay_worker(3, 0.0)
    hit = sv.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                     comb_fn=SUM)
    assert hit.cached and hit.engine == "threaded"
    assert_identical(hit.bufs, ref.bufs)


def test_irregular_templates_replay_jitted():
    """bruck / two_level interleave sequential SEND/RECV rounds: the lowering
    freezes the round/phase structure into static routing tables and replays
    them jitted, byte-identical to the threaded reference."""
    for template in ("bruck", "two_level"):
        workers = workers_for(template)
        bufs = make_bufs(workers, "uniform")
        sv = _jax_service()
        hit = _run_twice(sv, template, bufs, workers, comb_fn=SUM)
        assert hit.cached and hit.engine == "jax"
        assert hit.fallback_reason is None
        ref = _run_twice(service_for("threaded"), template, bufs, workers,
                         comb_fn=SUM)
        assert_identical(hit.bufs, ref.bufs)


def test_exotic_part_fn_falls_back_to_vectorized():
    """A partFunc outside the jnp registry (hash / range[k]) cannot be
    replicated inside the jitted program — but the numpy replay runs it."""
    mod = PartFn("mod", lambda keys, ndst: keys % ndst)
    bufs = make_bufs(WORKERS, "uniform")
    sv = _jax_service()
    hit = _run_twice(sv, "vanilla_push", bufs, WORKERS, part_fn=mod,
                     comb_fn=SUM)
    assert hit.cached and hit.engine == "vectorized"


# ---------------------------------------------------------------------------
# knob resolution: per-call > per-tenant > cluster
# ---------------------------------------------------------------------------

def test_executor_knob_stack():
    cluster = TeShuCluster(make_topology())           # fleet default: vectorized
    ml = cluster.tenant("ml", executor="jax")
    etl = cluster.tenant("etl")
    bufs = make_bufs(WORKERS, "uniform")
    assert _run_twice(ml, "vanilla_push", bufs, WORKERS,
                      comb_fn=SUM).engine == "jax"
    assert _run_twice(etl, "vanilla_push", bufs, WORKERS,
                      comb_fn=SUM).engine == "vectorized"
    # per-call overrides beat both tenant and cluster defaults
    assert ml.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                      comb_fn=SUM, executor="vectorized"
                      ).engine == "vectorized"
    assert etl.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                       comb_fn=SUM, executor="jax").engine == "jax"


def test_executor_knob_validation():
    with pytest.raises(ValueError):
        TeShuService(make_topology(), executor="cuda")
    cluster = TeShuCluster(make_topology())
    with pytest.raises(ValueError):
        cluster.tenant("bad", executor="cuda")


# ---------------------------------------------------------------------------
# lowering lifetime
# ---------------------------------------------------------------------------

def test_lowering_is_attached_to_the_cached_plan():
    """The routing tables are derived once and frozen onto the plan: later
    hits reuse the same JaxLowering object (plan-cache lifetime, no rebuild)."""
    bufs = make_bufs(WORKERS, "uniform")
    sv = _jax_service()
    hit = _run_twice(sv, "network_aware", bufs, WORKERS, comb_fn=SUM)
    assert hit.engine == "jax"
    (key, plan), = sv.plan_cache._spaces["default"].plans.items()
    low = get_lowering(plan)
    assert low is not None
    assert low.gsize.shape[0] == len(plan.levels)
    sv.shuffle("network_aware", copy_bufs(bufs), WORKERS, WORKERS, comb_fn=SUM)
    assert get_lowering(plan) is low                  # reused, not rebuilt


def test_lower_plan_declines_unsupported_shapes():
    """bruck's lowering is a ring simulation: a plan whose destination set is
    not the source ring has no static round structure to freeze."""
    import dataclasses

    bufs = make_bufs(WORKERS, "uniform")
    sv = service_for("threaded")
    _run_twice(sv, "bruck", bufs, WORKERS, comb_fn=SUM)
    (_, plan), = sv.plan_cache._spaces["default"].plans.items()
    assert lower_plan(plan) is not None               # the real ring lowers
    broken = dataclasses.replace(plan, dsts=tuple(WORKERS[:4]))
    assert plan_decline(broken) == "ring_mismatch"
    assert lower_plan(broken) is None


# ---------------------------------------------------------------------------
# batched multi-tenant dispatch
# ---------------------------------------------------------------------------

def _batch_cluster():
    cl = TeShuCluster(make_topology(), execution="auto", executor="jax")
    return cl, [cl.tenant(f"t{i}") for i in range(4)]


def test_batched_dispatch_matches_serial():
    """>=4 same-signature wfair submissions execute as ONE vmapped dispatch:
    outputs byte-identical to serial, per-tenant byte lanes split exactly as
    serial (cost lanes to the ulp), and the shared epoch makes the batch's
    modelled cost strictly cheaper than four serial jax hits."""
    bufs = make_bufs(WORKERS, "zipf")

    def run(batched):
        cl, tenants = _batch_cluster()
        for t in tenants:                       # warm: plan + trace per tenant
            t.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                      comb_fn=SUM)
            t.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                      comb_fn=SUM)
        snap0 = cl.cluster.ledger.snapshot()
        if batched:
            tickets = [t.submit("vanilla_push", copy_bufs(bufs), WORKERS,
                                WORKERS, comb_fn=SUM) for t in tenants]
            results = cl.run_pending()
            out = [results[tk] for tk in tickets]
        else:
            out = [t.shuffle("vanilla_push", copy_bufs(bufs), WORKERS,
                             WORKERS, comb_fn=SUM) for t in tenants]
        return cl, out, snap0, cl.cluster.ledger.snapshot()

    _, serial, s0, s1 = run(False)
    clb, batch, b0, b1 = run(True)
    (entry,) = clb.last_schedule()["batches"]
    assert entry["template"] == "vanilla_push" and entry["size"] == 4
    for r_s, r_b in zip(serial, batch):
        assert r_s.engine == "jax" and not r_s.batched
        assert r_b.engine == "jax" and r_b.batched and r_b.cached
        assert r_b.fallback_reason is None
        assert_identical(r_b.bufs, r_s.bufs)
    for lane, exact in (("bytes_per_tenant", True), ("cost_per_tenant", False)):
        ds = {k: s1[lane][k] - s0[lane].get(k, 0) for k in s1[lane]}
        db = {k: b1[lane][k] - b0[lane].get(k, 0) for k in b1[lane]}
        assert set(ds) == set(db)
        for k in ds:
            if exact:
                assert ds[k] == db[k], (lane, k, ds[k], db[k])
            else:                               # running float sum: ulp noise
                assert math.isclose(ds[k], db[k], rel_tol=1e-9,
                                    abs_tol=1e-18), (lane, k, ds[k], db[k])
    assert (b1["modelled_time_s"] - b0["modelled_time_s"]) \
        < (s1["modelled_time_s"] - s0["modelled_time_s"])


def test_batch_member_declines_with_its_own_reason():
    """A submission that cannot join the vmapped dispatch (here: a partFunc
    outside the jnp registry) runs solo and reports its OWN reason code —
    not a batch-level code, and not another member's."""
    mod = PartFn("mod", lambda keys, ndst: keys % ndst)
    cl, tenants = _batch_cluster()
    bufs = make_bufs(WORKERS, "uniform")
    for t in tenants[:3]:
        for _ in range(2):
            t.shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                      comb_fn=SUM)
    for _ in range(2):
        tenants[3].shuffle("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                           part_fn=mod, comb_fn=SUM)
    tickets = [t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                        comb_fn=SUM) for t in tenants[:3]]
    odd_ticket = tenants[3].submit("vanilla_push", copy_bufs(bufs), WORKERS,
                                   WORKERS, part_fn=mod, comb_fn=SUM)
    results = cl.run_pending()
    (entry,) = cl.last_schedule()["batches"]
    assert entry["size"] == 3                   # the odd one never joined
    for tk in tickets:
        assert results[tk].engine == "jax" and results[tk].batched
    odd = results[odd_ticket]
    assert odd.engine == "vectorized" and not odd.batched
    assert odd.fallback_reason == "unsupported_part_fn"


STREAM_ROWS = 8_000


def _lineitem(seed: int, hot: int = 0, rows: int = STREAM_ROWS):
    """A small seeded lineitem-like table: dbgen's sparse order keys with
    1-7 lines per order (``hot`` of the rows on order 1, generated first;
    ``hot=-1``: every order one line), and quantity, price, discount and tax
    as float64 hundredths, so every sum is an exact integer."""
    rng = np.random.default_rng(seed)
    if hot < 0:
        orders = np.arange(1, rows + 1, dtype=np.int64)
    else:
        orders = np.concatenate([
            np.ones(hot, np.int64),
            np.repeat(np.arange(2, rows + 2, dtype=np.int64),
                      rng.integers(1, 8, rows))[:rows - hot]])
    keys = ((orders >> 3) << 5) | (orders & 7)
    vals = np.stack([rng.integers(100, 5_001, rows),
                     rng.integers(90_000, 10_500_001, rows),
                     rng.integers(0, 11, rows), rng.integers(0, 9, rows)],
                    axis=1).astype(np.float64)
    return keys, vals


def _table_bufs(table):
    """The table spread evenly over the workers in row order."""
    keys, vals = table
    parts = np.array_split(np.arange(keys.size), len(WORKERS))
    return {w: Msgs(keys[p], vals[p]) for w, p in zip(WORKERS, parts)}


def _numpy_group_by(table, comb: str):
    keys, vals = table
    uniq, inv = np.unique(keys, return_inverse=True)
    ufunc, start = {"sum": (np.add, 0.0), "min": (np.minimum, np.inf),
                    "max": (np.maximum, -np.inf)}[comb]
    out = np.full((uniq.size, vals.shape[1]), start)
    ufunc.at(out, inv, vals)
    return uniq, out


def _delivered(res):
    """Every destination's rows, sorted by key; a key on two destinations
    shows as a repeated key."""
    keys = np.concatenate([m.keys for m in res.bufs.values()])
    vals = np.concatenate([m.vals for m in res.bufs.values()])
    order = np.argsort(keys, kind="stable")
    return keys[order], vals[order]


def _stream_passes(template, comb, tables, passes, tracing, rotate=True):
    """Four tenants (``s0``..``s3``) each submit a shuffle of a table, then
    one ``run_pending()`` per pass: in pass ``r`` tenant ``t`` takes table
    ``(r + t) % 4`` (``rotate``) or always table ``t``.  Returns the cluster
    and, per pass, each tenant's (table index, result)."""
    cl = TeShuCluster(make_topology(), execution="auto", executor="jax",
                      tracing=tracing)
    tenants = [cl.tenant(f"s{t}") for t in range(4)]
    out = []
    for r in range(passes):
        idx = [(r + t) % 4 if rotate else t for t in range(4)]
        tickets = [c.submit(template, _table_bufs(tables[i]), WORKERS,
                            WORKERS, comb_fn=COMBINERS[comb])
                   for c, i in zip(tenants, idx)]
        got = cl.run_pending()
        out.append([(i, got[tk]) for i, tk in zip(idx, tickets)])
    return cl, out


@pytest.mark.parametrize("template", ["vanilla_push", "network_aware"])
@pytest.mark.parametrize("comb", ["sum", "min", "max"])
def test_four_streams_batched_match_numpy_group_by(template, comb):
    """TPC-H's throughput test in small: four tenants submit a group-by of
    their own table each pass, one ``run_pending()`` runs the pass as ONE
    vmapped dispatch, and every member delivers exactly the numpy group-by
    of its own table, bit for bit, with tracing on and off alike."""
    tables = [_lineitem(seed) for seed in range(4)]
    refs = [_numpy_group_by(t, comb) for t in tables]
    delivered = {}
    for tracing in (False, True):
        cl, passes = _stream_passes(template, comb, tables, 5, tracing)
        batches = cl.obs.metrics.get("teshu_batched_dispatches_total",
                                     template=template)
        assert batches == 4                 # pass 0 instantiates the plans
        for r, members in enumerate(passes[1:], start=1):
            for i, res in members:
                assert res.engine == "jax" and res.batched
                assert res.fallback_reason is None
                keys, vals = _delivered(res)
                assert np.array_equal(keys, refs[i][0])
                assert np.array_equal(vals.view(np.int64),
                                      refs[i][1].view(np.int64))
                delivered.setdefault(tracing, []).append((keys, vals))
        if tracing:
            jit = [s for s in cl.spans() if s["name"] == "jit_replay"]
            assert [s["attrs"]["batch"] for s in jit] == [4] * 4
    for (k_off, v_off), (k_on, v_on) in zip(delivered[False],
                                            delivered[True]):
        assert np.array_equal(k_off, k_on)
        assert np.array_equal(v_off.view(np.int64), v_on.view(np.int64))


@pytest.mark.parametrize("template", ["vanilla_push", "network_aware"])
@pytest.mark.parametrize("comb", ["sum", "max"])
def test_batched_members_whose_longest_segments_differ_1000x(template, comb):
    """One vmapped fold serves members whose longest equal-key segments
    differ a thousandfold (every order one line, against 2,000 lines on one
    order): the loop runs as long as the longest member needs, and each
    member still delivers its own numpy group-by exactly."""
    tables = [_lineitem(0, hot=-1), _lineitem(1, hot=-1),
              _lineitem(2, hot=2_000), _lineitem(3)]
    longest = []
    for keys, _ in tables:
        per_worker = [np.unique(b.keys, return_counts=True)[1].max()
                      for b in _table_bufs((keys, keys)).values()]
        longest.append(int(max(per_worker)))
    assert max(longest) >= 1_000 * min(longest)
    cl, passes = _stream_passes(template, comb, tables, 3, True,
                                rotate=False)
    for members in passes[1:]:
        for i, res in members:
            assert res.batched
            keys, vals = _delivered(res)
            ref = _numpy_group_by(tables[i], comb)
            assert np.array_equal(keys, ref[0])
            assert np.array_equal(vals.view(np.int64), ref[1].view(np.int64))
    rounds = [s["attrs"]["fold_rounds"] for s in cl.spans()
              if s["name"] == "jit_replay"][-1]
    assert max(rounds) >= 250 * min(rounds)
    if template == "vanilla_push":      # one COMB: its rounds are the longest
        assert rounds[2] >= 2_000 and rounds[0] == 1


def test_members_sharing_one_bufs_dict_get_their_own_slices():
    """Each member's replay is handed its own slice by ``run_pending``, not
    found through its buffers: two submissions of one tenant that share one
    ``bufs`` dict (the tables differ only by a shift of the payloads) each
    deliver their own group-by."""
    cl = TeShuCluster(make_topology(), execution="auto", executor="jax")
    a, b = cl.tenant("a"), cl.tenant("b")
    table = _lineitem(5)
    shared = _table_bufs(table)
    for _ in range(2):                              # plans, then the program
        for t in (a, b):
            t.submit("vanilla_push", shared, WORKERS, WORKERS, comb_fn=SUM)
        cl.run_pending()
    other = _table_bufs((table[0], table[1] + 100.0))
    tickets = [a.submit("vanilla_push", shared, WORKERS, WORKERS,
                        comb_fn=SUM),
               b.submit("vanilla_push", other, WORKERS, WORKERS,
                        comb_fn=SUM),
               b.submit("vanilla_push", shared, WORKERS, WORKERS,
                        comb_fn=SUM)]
    got = cl.run_pending()
    (entry,) = cl.last_schedule()["batches"]
    assert entry["size"] == 3
    for tk, vals in zip(tickets, (table[1], table[1] + 100.0, table[1])):
        assert got[tk].batched
        keys, out = _delivered(got[tk])
        ref = _numpy_group_by((table[0], vals), "sum")
        assert np.array_equal(keys, ref[0])
        assert np.array_equal(out.view(np.int64), ref[1].view(np.int64))


# ---------------------------------------------------------------------------
# dtypes / direct-call contract
# ---------------------------------------------------------------------------

def test_output_dtypes_are_exact():
    """x64 mode end-to-end: int64 keys, float64 payloads, bit-for-bit."""
    bufs = make_bufs(WORKERS, "uniform")
    hit = _run_twice(_jax_service(), "vanilla_pull", bufs, WORKERS,
                     comb_fn=SUM)
    assert hit.engine == "jax"
    for m in hit.bufs.values():
        assert m.keys.dtype == np.int64
        assert m.vals.dtype == np.float64


def test_try_run_jax_requires_a_plan():
    """Direct-call contract: no plan (fresh instantiation) => decline."""
    sv = _jax_service()
    from repro.core import HASH_PART, ShuffleArgs
    args = ShuffleArgs(template_id="vanilla_push", shuffle_id=1,
                       srcs=tuple(WORKERS), dsts=tuple(WORKERS),
                       part_fn=HASH_PART, comb_fn=SUM)
    bufs = make_bufs(WORKERS, "uniform")
    assert try_run_jax(sv.cluster, args, bufs) is None


# ---------------------------------------------------------------------------
# the COMB fold: rounds by rank within a segment vs the row-serial scan
# ---------------------------------------------------------------------------

def _scan_combine(comb, keys, vals, owner, alive, participate, sentinel):
    """The oracle: the replay's COMB as it was before the round fold, a
    ``lax.scan`` over every row (one row per step)."""
    import jax.numpy as jnp
    from jax import lax

    folds = participate & alive
    ckey = jnp.where(folds, keys, jnp.int64(0))
    perm = jnp.argsort(ckey, stable=True)
    so = jnp.where(alive, owner, sentinel)
    perm = perm[jnp.argsort(so[perm], stable=True)]
    keys, vals, owner, alive, folds = (
        keys[perm], vals[perm], owner[perm], alive[perm], folds[perm])
    prev_same = ((owner == jnp.roll(owner, 1))
                 & (keys == jnp.roll(keys, 1))).at[0].set(False)
    is_start = ~(prev_same & folds)
    op = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[comb]

    def fold(acc, x):
        v, start = x
        acc = jnp.where(start, v, op(acc, v))
        return acc, acc

    _, folded = lax.scan(fold, jnp.zeros_like(vals[0]), (vals, is_start))
    seg_end = jnp.concatenate([is_start[1:], jnp.ones((1,), bool)])
    return keys, folded, owner, alive & seg_end


FOLD_ROWS = 6000
FOLD_OWNERS = 4
# one segment per length: each side of every fold phase's first round
# (1, 8, 64, 512, 4096), the longest crossing them all
PHASE_EDGES = (2, 8, 9, 64, 65, 512, 513, 4100)


def _segment_lengths(shape, rng):
    n = FOLD_ROWS
    if shape == "singletons":
        return np.ones(n, np.int64)
    if shape == "short":
        lens = rng.integers(1, 8, n)
        return lens[:np.searchsorted(np.cumsum(lens), n) + 1]
    if shape == "one":
        return np.array([n])
    if shape in ("pairs", "nines"):          # fill phase 1's / 2's window
        size = 2 if shape == "pairs" else 9
        return np.full(-(-n // size), size)
    lens = list(PHASE_EDGES)                 # "zipf": Zipf(1.5) lengths
    while sum(lens) < n:
        lens.append(int(min(rng.zipf(1.5), 300)))
    return np.array(lens)


def _fold_case(comb, shape, participation, seed):
    """Rows of segments of the given lengths (a unique key each, a random
    owner), shuffled, with float64 payloads holding -0.0 (and NaN for MIN
    and MAX), some rows already dead, and all owners folding or only owners
    0 and 1 (a staged level's non-participating owners)."""
    rng = np.random.default_rng(seed)
    lens = _segment_lengths(shape, rng)
    seg = np.repeat(np.arange(len(lens)), lens)[:FOLD_ROWS]
    seg_owner = rng.integers(0, FOLD_OWNERS, len(lens))
    perm = rng.permutation(FOLD_ROWS)
    keys = (seg[perm] * 7 + 3).astype(np.int64)
    owner = seg_owner[seg[perm]].astype(np.int32)
    vals = rng.standard_normal((FOLD_ROWS, 3))
    vals[rng.random(vals.shape) < 0.05] = -0.0
    if comb != "sum":
        vals[rng.random(vals.shape) < 0.02] = np.nan
    alive = rng.random(FOLD_ROWS) > 0.03
    participate = (np.ones(FOLD_ROWS, bool) if participation == "all"
                   else owner < 2)
    return keys, vals, owner, alive, participate


def _longest(keys, owner, alive, participate):
    """The longest (owner, key) segment among folding rows; 1 for none."""
    f = alive & participate
    pairs = np.stack([owner[f].astype(np.int64), keys[f]], axis=1)
    if not len(pairs):
        return 1
    return int(np.unique(pairs, axis=0, return_counts=True)[1].max())


def _assert_same_fold(got, want):
    g_keys, g_vals, g_owner, g_alive = (np.asarray(a) for a in got)
    w_keys, w_vals, w_owner, w_alive = (np.asarray(a) for a in want)
    np.testing.assert_array_equal(g_keys, w_keys)
    np.testing.assert_array_equal(g_owner, w_owner)
    np.testing.assert_array_equal(g_alive, w_alive)
    # every live row is a segment end: its fold, byte for byte
    assert np.array_equal(g_vals[g_alive].view(np.uint64),
                          w_vals[w_alive].view(np.uint64))


def _jitted_folds(comb):
    import jax

    new = jax.jit(lambda *a: _combine(comb, *a, FOLD_OWNERS))
    old = jax.jit(lambda *a: _scan_combine(comb, *a, FOLD_OWNERS))
    return new, old


@pytest.mark.parametrize("participation", ["all", "owners01"])
@pytest.mark.parametrize("shape", ["singletons", "short", "pairs", "nines",
                                   "one", "zipf"])
@pytest.mark.parametrize("comb", ["sum", "min", "max"])
def test_round_fold_matches_serial_scan(comb, shape, participation):
    """Segment ends are byte-identical to the row-serial scan's (same IEEE
    operations in the same order: -0.0 and NaN behave alike), and the fold
    ran as many rounds as the longest segment."""
    import jax

    case = _fold_case(comb, shape, participation, seed=len(shape))
    new, old = _jitted_folds(comb)
    with jax.enable_x64(True):
        *got, rounds = new(*case)
        want = old(*case)
    _assert_same_fold(got, want)
    keys, _, owner, alive, participate = case
    assert int(rounds) == _longest(keys, owner, alive, participate)


@pytest.mark.parametrize("comb", ["sum", "min", "max"])
def test_round_fold_under_vmap(comb):
    """The batched program's fold (``prepare_batch``): two members whose
    longest segments differ (at most 7 rows and about 4,100) fold in one
    vmapped loop,
    each byte-identical to its own serial scan, each with its own rounds."""
    import jax

    cases = [_fold_case(comb, "short", "all", seed=1),
             _fold_case(comb, "zipf", "all", seed=2)]
    stacked = [np.stack(parts) for parts in zip(*cases)]
    new, old = _jitted_folds(comb)
    with jax.enable_x64(True):
        *got, rounds = jax.vmap(
            lambda *a: _combine(comb, *a, FOLD_OWNERS))(*stacked)
        for i, case in enumerate(cases):
            _assert_same_fold([a[i] for a in got], old(*case))
    assert np.asarray(rounds).tolist() == [
        _longest(keys, owner, alive, part)
        for keys, _, owner, alive, part in cases]
    assert len(set(np.asarray(rounds).tolist())) == 2


def test_jit_replay_reports_fold_rounds():
    """``jit_replay`` carries ``fold_rounds``: a push replay's one global
    COMB folds as many rounds as the most repeated key; a concat replay
    folds none; a batched dispatch reports each member's."""
    sv = _jax_service(tracing=True)
    bufs = make_bufs(WORKERS, "zipf", n=283)
    keys = np.concatenate([m.keys for m in bufs.values()])
    most = int(np.unique(keys, return_counts=True)[1].max())
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM, shuffle_id=71)
    _run_twice(sv, "vanilla_push", bufs, WORKERS, shuffle_id=72)
    for sid, rounds in ((71, most), (72, 0)):
        jit = [s for s in sv.spans(sid) if s["name"] == "jit_replay"]
        assert [s["attrs"]["fold_rounds"] for s in jit] == [rounds]

    cl = TeShuCluster(make_topology(), execution="auto", executor="jax",
                      tracing=True)
    tenants = [cl.tenant(f"t{i}") for i in range(2)]
    for t in tenants:
        _run_twice(t, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    cl.obs.tracer.clear()
    for t in tenants:
        t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                 comb_fn=SUM)
    cl.run_pending()
    (jit,) = [s for s in cl.spans() if s["name"] == "jit_replay"]
    assert jit["attrs"]["batch"] == 2
    assert jit["attrs"]["fold_rounds"] == [most, most]


# ---------------------------------------------------------------------------
# the wire format: 64-bit row arrays cross host<->device as uint32 words
# ---------------------------------------------------------------------------

# NaNs with payload bits (signalling and quiet, both signs), -0.0, +0.0,
# +-inf, the least and the greatest subnormal, the greatest double, 1.0
EDGE_BITS = np.array([0x7FF0000000000001, 0xFFF8000000000123,
                      0x8000000000000000, 0x0, 0x7FF0000000000000,
                      0xFFF0000000000000, 0x1, 0x800FFFFFFFFFFFFF,
                      0x7FEFFFFFFFFFFFFF, 0x3FF0000000000000], np.uint64)


def _edge_bufs(workers):
    """``make_bufs``' rows, and on every worker rows whose payloads are the
    edge bit patterns, each under a key of its own: negative keys, and on
    the first worker +-(2**63 - 1).  No COMB folds a key that occurs once,
    so these payloads must arrive bit for bit."""
    edge = EDGE_BITS.view(np.float64)
    out = {}
    for i, (w, m) in enumerate(make_bufs(workers, "zipf").items()):
        keys = -(np.arange(edge.size, dtype=np.int64) + 1 + 100 * i) * 7919
        if i == 0:
            keys[:2] = (2**63 - 1, -(2**63 - 1))
        vals = np.stack([np.roll(edge, i), np.roll(edge, i + 3)], axis=1)
        out[w] = Msgs(np.concatenate([m.keys, keys]),
                      np.concatenate([m.vals, vals]))
    return out


def _assert_bits_identical(a: dict, b: dict):
    """Per destination, keys and payload bits equal in physical row order
    (``assert_identical`` takes NaN for NaN and -0.0 for 0.0)."""
    assert set(a) == set(b)
    for w in a:
        assert np.array_equal(a[w].keys, b[w].keys)
        assert np.array_equal(a[w].vals.view(np.uint64),
                              b[w].vals.view(np.uint64))


def _parent_outputs(spec, operands, batch):
    """The replay program as it ran with 64-bit operands and results
    crossing as they are, on the same host operands."""
    import jax

    impl = (jaxplan._two_level_impl if spec.template == "two_level"
            else jaxplan._replay_impl)

    def run(spec, keys, vals, owner, *shared):
        if batch:
            return jax.vmap(lambda k, v, o: impl(spec, k, v, o, *shared))(
                keys, vals, owner)
        return impl(spec, keys, vals, owner, *shared)
    with jax.enable_x64(True):
        out = jax.jit(run, static_argnums=0)(spec, *operands)
    return [np.asarray(a) for a in out[:-1]]


@pytest.mark.parametrize("path", ["serial", "batched"])
@pytest.mark.parametrize("template", ALL_TEMPLATES)
def test_edge_bit_patterns_cross_the_wire_unchanged(template, path,
                                                     monkeypatch):
    """Keys at the int64 extremes and below zero, and payloads of NaNs with
    payload bits, signed zeros, infinities and subnormals, replayed serially
    and as a batch: the delivered buffers match the vectorized executor bit
    for bit, and every program result (keys, vals, owner, alive, the flow
    counts) reaches the host as the program computed it without the wire
    format."""
    workers = workers_for(template)
    bufs = _edge_bufs(workers)
    calls = []
    dispatch = jaxplan._dispatch

    def spy(tracer, fn, spec, operands, ids, rows, **attrs):
        out = dispatch(tracer, fn, spec, operands, ids, rows, **attrs)
        calls.append((spec, operands, attrs.get("batch", 0), out))
        return out
    monkeypatch.setattr(jaxplan, "_dispatch", spy)
    if path == "serial":
        got = [_run_twice(_jax_service(), template, bufs, workers,
                          comb_fn=SUM)]
    else:
        cl = TeShuCluster(make_topology(), execution="auto", executor="jax")
        tenants = [cl.tenant(f"t{i}") for i in range(2)]
        for t in tenants:
            _run_twice(t, template, bufs, workers, comb_fn=SUM)
        tickets = [t.submit(template, copy_bufs(bufs), workers, workers,
                            comb_fn=SUM) for t in tenants]
        done = cl.run_pending()
        got = [done[tk] for tk in tickets]
        assert all(r.batched for r in got)
    ref = _run_twice(service_for("vectorized"), template, bufs, workers,
                     comb_fn=SUM)
    for res in got:
        assert res.engine == "jax" and res.fallback_reason is None
        _assert_bits_identical(res.bufs, ref.bufs)
        delivered = np.concatenate([m.vals for m in res.bufs.values()])
        assert set(EDGE_BITS.tolist()) <= set(
            delivered.view(np.uint64).ravel().tolist())
    spec, operands, batch, out = calls[-1]
    assert batch == (2 if path == "batched" else 0)
    want = _parent_outputs(spec, operands, batch)
    assert len(out) == len(want)
    for a, b in zip(out, want):      # owner and alive among them
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def test_transfers_count_their_uint32_words():
    """With tracing on, ``to_device`` and ``to_host`` carry ``words32``:
    the 64-bit arrays that crossed as uint32 words, keys and payloads each
    way, for a serial replay and a batched dispatch alike."""
    sv = _jax_service(tracing=True)
    bufs = make_bufs(WORKERS, "zipf")
    _run_twice(sv, "vanilla_push", bufs, WORKERS, comb_fn=SUM, shuffle_id=81)
    spans = [s for s in sv.spans(81) if s["name"] in ("to_device", "to_host")]
    assert [(s["name"], s["attrs"]["words32"]) for s in spans] == [
        ("to_device", 2), ("to_host", 2)]

    cl = TeShuCluster(make_topology(), execution="auto", executor="jax",
                      tracing=True)
    tenants = [cl.tenant(f"t{i}") for i in range(2)]
    for t in tenants:
        _run_twice(t, "vanilla_push", bufs, WORKERS, comb_fn=SUM)
    cl.obs.tracer.clear()
    for t in tenants:
        t.submit("vanilla_push", copy_bufs(bufs), WORKERS, WORKERS,
                 comb_fn=SUM)
    cl.run_pending()
    spans = [s for s in cl.spans() if s["name"] in ("to_device", "to_host")]
    assert [(s["name"], s["attrs"]["batch"], s["attrs"]["words32"])
            for s in spans] == [("to_device", 2, 2), ("to_host", 2, 2)]


def _wire_round_trip(x: np.ndarray, columns: int) -> np.ndarray:
    """``x`` through the wire format both ways: to words on the host, to
    64-bit on the device and back to words, joined on the host."""
    import jax

    jaxplan._register_words()
    words = jaxplan._to_words(x, columns)
    with jax.enable_x64(True):
        back = jax.jit(lambda w: jaxplan._pack(jaxplan._unpack(w), w))(words)
    assert isinstance(back, jaxplan._Words) and back.pairs == words.pairs
    got = jaxplan._host_array(back)
    assert got.flags.c_contiguous and got.dtype == x.dtype
    return got


def test_float32_pairs_cross_what_a_tpu_holds(monkeypatch):
    """Where float64 crosses as float32 pairs (on a TPU, which holds a
    float64 as one), the host splits each value into its nearest float32
    and the nearest float32 to the rest, the device sums the pair and
    splits its result the same way, and the host sums that pair.  Here on
    the CPU: a value the pair holds exactly (integers below 2**48, 0.0,
    infinities, a pair 40 binades apart) comes back bit for bit, any other
    as the sum of its pair (-0.0 as 0.0, as the TPU runtime's own split
    and join give it), and int64 keys still cross as their bits."""
    monkeypatch.setattr(jaxplan, "_float_pairs", lambda: True)
    rng = np.random.default_rng(3)
    exact = np.concatenate([
        rng.integers(-2**48, 2**48, 5000).astype(np.float64),
        [0.0, np.inf, -np.inf, 2.0**100 + 2.0**60, -(2.0**-90), 1.0]])
    got = _wire_round_trip(exact.reshape(-1, 2), 2)
    assert np.array_equal(got.view(np.uint64).ravel(), exact.view(np.uint64))
    got = _wire_round_trip(np.array([[-0.0]]), 1)
    assert got.view(np.uint64)[0, 0] == 0

    x = rng.standard_normal((4000, 3)) * 10.0 ** rng.integers(-6, 9, (4000, 3))
    hi = x.astype(np.float32)
    pair = hi.astype(np.float64) + (x - hi).astype(np.float32)
    got = _wire_round_trip(x, 3)
    assert np.array_equal(got, pair) and not np.array_equal(got, x)
    assert np.isnan(_wire_round_trip(np.full((3, 1), np.nan), 1)).all()

    keys = np.array([2**63 - 1, -(2**63 - 1), -1, 0], np.int64)
    got = _wire_round_trip(keys, 1)
    assert np.array_equal(got, keys)


@pytest.mark.parametrize("path", ["serial", "batched"])
def test_replay_with_float32_pairs_matches_vectorized(path, monkeypatch):
    """Every template replays with its payloads crossing as float32 pairs,
    serially and as a batch, and delivers the vectorized executor's buffers
    bit for bit where the pairs hold every value exactly (integer payloads
    whose sums stay below 2**48)."""
    monkeypatch.setattr(jaxplan, "_float_pairs", lambda: True)
    for template in ALL_TEMPLATES:
        workers = workers_for(template)
        bufs = {w: Msgs(m.keys, np.floor(m.vals * 2**30))
                for w, m in make_bufs(workers, "zipf").items()}
        if path == "serial":
            got = [_run_twice(_jax_service(), template, bufs, workers,
                              comb_fn=SUM)]
        else:
            cl = TeShuCluster(make_topology(), execution="auto",
                              executor="jax")
            tenants = [cl.tenant(f"t{i}") for i in range(2)]
            for t in tenants:
                _run_twice(t, template, bufs, workers, comb_fn=SUM)
            tickets = [t.submit(template, copy_bufs(bufs), workers, workers,
                                comb_fn=SUM) for t in tenants]
            done = cl.run_pending()
            got = [done[tk] for tk in tickets]
            assert all(r.batched for r in got)
        ref = _run_twice(service_for("vectorized"), template, bufs, workers,
                         comb_fn=SUM)
        for res in got:
            assert res.engine == "jax" and res.fallback_reason is None
            _assert_bits_identical(res.bufs, ref.bufs)
