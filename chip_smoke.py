"""Run the served shuffle path once on one TPU chip and check what it returns.

    python chip_smoke.py               # TPC-H lineitem aggregation shuffle, one chip
    python chip_smoke.py --wire        # only the replay's float64 wire format
    python chip_smoke.py --four-chip   # expert-parallel MoE dispatch, 4 chips vs 1

The default phase drives ``TeShuCluster`` -> ``tenant()`` -> ``shuffle()`` /
``submit()`` + ``run_pending()`` over the 16 workers of a two-rack datacenter
with a ``lineitem`` table generated from ``--seed`` with dbgen's
distributions, keyed by ``l_orderkey`` and summing the four numeric columns.
Every output is checked against a plain numpy group-by written here, apart
from ``repro.core``.  Then the replay's wire format for float64 (the words
its keys and payloads cross in) is held to the runtime's own transfers bit
for bit, on general doubles and edge patterns and on whole replays of
non-integer payloads; ``--wire`` runs that phase alone.  ``--four-chip`` runs only one MoE FFN layer of
qwen3-moe-235b-a22b expert-parallel over a 2x2 mesh and the same layer on one
chip.

Earlier stdout lines are one JSON record per phase.  The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed.  The script exits non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# TPC-H scale factors (dbgen: SF1 = 1,500,000 orders, 6,001,215 lineitem
# rows).  The batched pass stacks two tenants' tables into one program: at
# SF1 the v5e compiler refuses it (22.92 GB of 15.75 GB HBM, the float64
# payload column padded to 128 lanes), so that pass runs at SF 0.6.
SCALE_FACTOR = 1.0
BATCH_SCALE_FACTOR = 0.6
SF1_LINEITEM_ROWS = 6_001_215
TEMPLATES = ("vanilla_push", "network_aware")
STEADY_CALLS = 2


def _log(**rec) -> None:
    print(json.dumps(rec), flush=True)


def use_compile_cache() -> str:
    """Persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache`` (a fixed path, so later runs hit it)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts programs lowered for compilation (persistent-cache hits
    included) since construction."""

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, _secs, **_kw):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# The data: TPC-H lineitem, dbgen's distributions (TPC-H spec 4.2.3)
# ---------------------------------------------------------------------------

def lineitem(sf: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``l_orderkey`` and the four numeric columns of ``lineitem`` at scale
    factor ``sf``, in generation order (an order's lines are adjacent).

    ``sf * 1.5 M`` orders with 1-7 lines each; at SF1 the line counts are
    nudged to dbgen's 6,001,215 rows.  Order keys are sparse as dbgen makes
    them (8 of every 32).  The DECIMAL columns are carried in hundredths
    (quantity 1-50, extended price = quantity x the part's retail price,
    discount 0.00-0.10, tax 0.00-0.08), so float64 sums are exact integers,
    as TPC-H's decimal arithmetic is, whatever order they fold in.
    """
    rng = np.random.default_rng(seed)
    n_orders = int(round(sf * 1_500_000))
    lines = rng.integers(1, 8, n_orders)
    if sf == 1.0:
        diff = SF1_LINEITEM_ROWS - int(lines.sum())
        step = 1 if diff > 0 else -1
        room = np.nonzero(lines < 7 if step > 0 else lines > 1)[0]
        lines[rng.choice(room, abs(diff), replace=False)] += step
    idx = np.arange(1, n_orders + 1, dtype=np.int64)
    orderkey = ((idx >> 3) << 5) | (idx & 7)           # dbgen mk_sparse
    keys = np.repeat(orderkey, lines)
    n = keys.size
    partkey = rng.integers(1, int(sf * 200_000) + 1, n)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    quantity = rng.integers(1, 51, n)
    vals = np.empty((n, 4), np.float64)
    vals[:, 0] = quantity * 100
    vals[:, 1] = quantity * retail_cents
    vals[:, 2] = rng.integers(0, 11, n)
    vals[:, 3] = rng.integers(0, 9, n)
    return keys, vals


def group_by_sum(keys: np.ndarray, vals: np.ndarray):
    """The plain reference: distinct keys ascending and their column sums."""
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.stack([np.bincount(inv, weights=vals[:, c], minlength=uniq.size)
                     for c in range(vals.shape[1])], axis=1)
    return uniq, sums


def check_outputs(bufs: dict, ref) -> str | None:
    """None when ``bufs`` (destination -> Msgs) is the exact group-by: every
    key on exactly one destination, the union of keys exact, every sum
    bit-identical.  Otherwise the first failure, in words."""
    uniq, sums = ref
    keys = np.concatenate([np.asarray(m.keys) for m in bufs.values()])
    vals = np.concatenate([np.asarray(m.vals).reshape(-1, sums.shape[1])
                           for m in bufs.values()])
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    if keys.size != uniq.size:
        dup = int(keys.size - np.unique(keys).size)
        return (f"{keys.size} output rows for {uniq.size} keys "
                f"({dup} keys on more than one destination)")
    if not np.array_equal(keys, uniq):
        return "output key set differs from the reference"
    if not np.array_equal(vals, sums):
        bad = int(np.any(vals != sums, axis=1).sum())
        return f"{bad} keys with sums that differ from the reference"
    return None


# ---------------------------------------------------------------------------
# Phase 1: the served shuffle path on one chip
# ---------------------------------------------------------------------------

def _table(sf: float, seed: int, workers: list[int]):
    """The lineitem table spread evenly over ``workers`` in generation order
    (a table scan split into equal partitions), and its reference group-by."""
    from repro.core import Msgs

    t0 = time.perf_counter()
    keys, vals = lineitem(sf, seed)
    ref = group_by_sum(keys, vals)
    parts = np.array_split(np.arange(keys.size), len(workers))
    bufs = {w: Msgs(keys[p], vals[p]) for w, p in zip(workers, parts)}
    _log(phase="data", sf=sf, rows=int(keys.size), groups=int(ref[0].size),
         input_bytes=int(keys.nbytes + vals.nbytes),
         seconds=time.perf_counter() - t0)
    return bufs, ref


def served_phase(sf: float, batch_sf: float, seed: int) -> list[str]:
    """Drive the served path; returns the failures (empty when it passed)."""
    import jax

    from repro.core import SUM, TeShuCluster, datacenter

    failures: list[str] = []
    compiles = CompileCounter()
    topo = datacenter(workers_per_server=4, servers_per_rack=2, racks=2,
                      oversubscription=4.0)
    workers = list(range(topo.num_workers))
    bufs, ref = _table(sf, seed, workers)

    def copy():
        return {w: m.copy() for w, m in bufs.items()}

    def verdict(label, res, want_engine):
        err = check_outputs(res.bufs, ref)
        if err is not None:
            failures.append(f"{label}: {err}")
        if want_engine is not None and (res.engine != want_engine
                                        or res.fallback_reason is not None):
            failures.append(f"{label}: engine={res.engine} "
                            f"fallback_reason={res.fallback_reason}")
        return err is None

    cluster = TeShuCluster(topo)
    q18 = cluster.tenant("q18")
    for template in TEMPLATES:
        calls = ["instantiate", "first_replay"] + [
            f"steady_{i}" for i in range(STEADY_CALLS)]
        for call in calls:
            n0 = compiles.n
            t = time.perf_counter()
            res = q18.shuffle(template, copy(), workers, workers, comb_fn=SUM)
            secs = time.perf_counter() - t
            ok = verdict(f"{template}/{call}", res,
                         None if call == "instantiate" else "jax")
            ncomp = compiles.n - n0
            if call.startswith("steady") and ncomp:
                failures.append(f"{template}/{call}: {ncomp} compiles")
            _log(phase="served", template=template, call=call,
                 engine=res.engine, fallback_reason=res.fallback_reason,
                 cached=res.cached, seconds=secs, compiles=ncomp, correct=ok)

    # two tenants submit the same-signature shuffle: one vmapped dispatch
    del bufs
    bufs, ref = _table(batch_sf, seed, workers)
    tenants = (q18, cluster.tenant("q18_b"))
    for c in tenants:                  # each tenant's own plan namespace
        t = time.perf_counter()
        res = c.shuffle(TEMPLATES[0], copy(), workers, workers, comb_fn=SUM)
        _log(phase="batched", call="instantiate", tenant=c.tenant_id,
             engine=res.engine, seconds=time.perf_counter() - t,
             correct=verdict(f"batched/{c.tenant_id}/instantiate", res, None))
    tickets = [c.submit(TEMPLATES[0], copy(), workers, workers, comb_fn=SUM)
               for c in tenants]
    n0 = compiles.n
    t = time.perf_counter()
    results = cluster.run_pending()
    secs = time.perf_counter() - t
    for ticket in tickets:
        res = results[ticket]
        ok = verdict(f"batched/{ticket}", res, "jax")
        if not res.batched:
            failures.append(f"batched/{ticket}: not batched")
        _log(phase="batched", ticket=ticket, engine=res.engine,
             fallback_reason=res.fallback_reason, batched=res.batched,
             correct=ok)
    _log(phase="batched", call="run_pending", sf=batch_sf, seconds=secs,
         compiles=compiles.n - n0)
    _log_device_memory(jax.devices()[:1])
    return failures


def _log_device_memory(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        _log(phase="device_memory", device=d.id,
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             bytes_limit=stats.get("bytes_limit"))


# ---------------------------------------------------------------------------
# Phase 2: the replay's wire format against the runtime's own transfers
# ---------------------------------------------------------------------------

WIRE_VALUES = 200_000

# float64 bit patterns at the edges: NaNs with payload bits (signalling and
# quiet, both signs), both zeros, both infinities, the least and greatest
# double subnormals and normals, float32's least subnormal and greatest
# finite as doubles, and a double just above float32's range
WIRE_EDGE_BITS = (0x7FF0000000000001, 0xFFF8000000000123, 0x7FF4000000000000,
                  0x0, 0x8000000000000000, 0x7FF0000000000000,
                  0xFFF0000000000000, 0x1, 0x800FFFFFFFFFFFFF,
                  0x0010000000000000, 0x7FEFFFFFFFFFFFFF, 0x36A0000000000000,
                  0x47EFFFFFE0000000, 0x47F0000000000000)


def wire_values(seed: int) -> dict:
    """Float64 test values by kind: random bits whose float32 pair lies in
    float32's normal range, random bits whose pair's low part falls below
    it, decimal hundredths (``12345.67``), doubles below float32's range
    (double subnormals among them) and above it, and the edge patterns."""
    rng = np.random.default_rng(seed)
    n = WIRE_VALUES

    def doubles(exp_lo, exp_hi):
        sign = rng.integers(0, 2, n, dtype=np.uint64) << np.uint64(63)
        exp = rng.integers(exp_lo, exp_hi, n, dtype=np.uint64) << np.uint64(52)
        frac = rng.integers(0, 2**52, n, dtype=np.uint64)
        return (sign | exp | frac).view(np.float64)
    return {
        "general": doubles(1023 - 101, 1023 + 128),
        "low_part_below_float32": doubles(1023 - 126, 1023 - 101),
        "hundredths": rng.integers(-10**13, 10**13, n) / 100,
        "below_float32": np.concatenate([doubles(0, 1),
                                         doubles(1, 1023 - 126)]),
        "above_float32": doubles(1023 + 128, 2047),
        "edge": np.array(WIRE_EDGE_BITS, np.uint64).view(np.float64),
    }


def _held_by_float32_arithmetic(x: np.ndarray):
    """What a TPU's float32 arithmetic keeps of each finite double ``x``:
    its float32 pair (high the nearest float32, low the nearest to the
    rest) with each part below float32's normal range flushed to zero; and
    where that flush changes the value (a nonzero ``x`` whose high part is
    zero or subnormal, or whose low part is subnormal)."""
    tiny = np.finfo(np.float32).tiny
    with np.errstate(over="ignore", invalid="ignore"):
        hi = x.astype(np.float32)
        lo = np.where(np.isfinite(hi), (x - hi).astype(np.float32), 0)
    sub_hi = np.isfinite(x) & (x != 0) & (np.abs(hi) < tiny)
    sub_lo = (lo != 0) & (np.abs(lo) < tiny)
    held = (np.where(np.abs(hi) < tiny, 0, hi).astype(np.float64)
            + np.where(np.abs(lo) < tiny, 0, lo))
    return held, sub_hi | sub_lo


def _wire_faults(x: np.ndarray, got: np.ndarray, runtime: np.ndarray,
                 pairs: bool) -> dict:
    """Values of ``got`` that break the wire's contract, and the first few
    as hex bits of (value, runtime's, got): each must carry the runtime's
    bits, save, where float64 crosses as float32 ``pairs``, a value whose
    pair has a part below float32's normal range, which may instead come
    back as float32 arithmetic holds it (the wire forms and splits the pair
    on the device; the runtime moves its parts as data)."""
    held, flushed = _held_by_float32_arithmetic(x)
    differ = got.view(np.uint64) != runtime.view(np.uint64)
    bad = np.where(flushed & pairs, differ & (got != held), differ)
    idx = np.nonzero(bad)[0]
    return {"n": int(idx.size), "flushed": int(flushed.sum()), "examples": [
        [f"{int(v.view(np.uint64)[i, 0]):#018x}" for v in (x, runtime, got)]
        for i in idx[:3]]}


def _replay_without_wire(spec, operands) -> list:
    """The replay program with its 64-bit operands and results crossing as
    they are, through the runtime's own transfers."""
    import jax

    from repro.core import jaxplan

    impl = (jaxplan._two_level_impl if spec.template == "two_level"
            else jaxplan._replay_impl)
    with jax.enable_x64(True):
        out = jax.jit(impl, static_argnums=0)(spec, *operands)
    return [np.asarray(a) for a in out[:-1]]


def wire_phase(seed: int, rows: int = 20_000) -> list[str]:
    """The float64 wire format (``jaxplan._Words``) against the runtime's
    own transfers (``jax.device_put`` in, ``np.asarray`` out) over
    ``wire_values``: the program's entry read back by the runtime, its
    exit fed by the runtime, and both ways as a replay moves a value,
    each bit for bit but where float32 arithmetic flushes a part of the
    value's pair (``_wire_faults``); then whole replays of non-integer
    payloads on ``rows`` rows, every output bit for bit.  Logs how far the
    runtime's round trip moved each kind of value (a TPU holds a float64
    as a float32 pair).  Returns the failures."""
    import jax

    from repro.core import SUM, Msgs, TeShuCluster, datacenter, jaxplan

    failures: list[str] = []
    jaxplan._register_words()
    with jax.enable_x64(True):
        entry = jax.jit(jaxplan._unpack)
        both = jax.jit(lambda w: jaxplan._pack(jaxplan._unpack(w), w))
        for kind, x in wire_values(seed).items():
            x = x.reshape(-1, 1)
            like = jaxplan._to_words(x, 1)
            runtime = np.asarray(jax.device_put(x))
            faults = {way: _wire_faults(x, got, runtime, like.pairs)
                      for way, got in (
                          ("entry", np.asarray(entry(like))),
                          ("exit", jaxplan._host_array(jax.jit(
                              lambda v: jaxplan._pack(v, like))(
                                  jax.device_put(x)))),
                          ("both_ways", jaxplan._host_array(both(like))))}
            moved = runtime != x
            with np.errstate(invalid="ignore"):
                err = np.abs(runtime - x)[moved & np.isfinite(x)]
                ulps = err / np.spacing(np.abs(x[moved & np.isfinite(x)]))
            _log(phase="wire", kind=kind, values=int(x.size),
                 pairs=like.pairs, faults=faults,
                 runtime_moved=int((runtime.view(np.uint64)
                                    != x.view(np.uint64)).sum()),
                 runtime_max_ulps=float(ulps.max()) if ulps.size else 0.0)
            failures += [f"wire/{kind}: the {way} breaks the contract on "
                         f"{f['n']} of {x.size} values"
                         for way, f in faults.items() if f["n"]]

    topo = datacenter(workers_per_server=4, servers_per_rack=2, racks=1)
    workers = list(range(topo.num_workers))
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, rows // 4, rows).astype(np.int64)
    vals = rng.standard_normal((rows, 4)) * 10.0 ** rng.integers(-3, 7, (rows, 4))
    parts = np.array_split(np.arange(rows), len(workers))
    calls = []
    dispatch = jaxplan._dispatch

    def spy(tracer, fn, spec, operands, *args, **kw):
        out = dispatch(tracer, fn, spec, operands, *args, **kw)
        calls.append((spec, operands, out))
        return out
    jaxplan._dispatch = spy
    try:
        client = TeShuCluster(topo).tenant("wire")
        for template in TEMPLATES:
            for _ in range(2):
                client.shuffle(template, {w: Msgs(keys[p], vals[p])
                                          for w, p in zip(workers, parts)},
                               workers, workers, comb_fn=SUM)
    finally:
        jaxplan._dispatch = dispatch
    for spec, operands, out in calls:
        want = _replay_without_wire(spec, operands)
        bad = [i for i, (a, b) in enumerate(zip(out, want))
               if a.dtype != b.dtype or a.tobytes() != b.tobytes()]
        _log(phase="wire", template=spec.template, rows=rows,
             outputs=len(out), outputs_differ=bad)
        if bad or len(out) != len(want):
            failures.append(f"wire/{spec.template}: outputs {bad} differ from "
                            "the replay without the wire format")
    if len(calls) != len(TEMPLATES):   # the first call of each instantiates
        failures.append(f"wire: {len(calls)} jitted replays, not "
                        f"{len(TEMPLATES)}")
    return failures


# ---------------------------------------------------------------------------
# Phase 3 (--four-chip): expert-parallel MoE dispatch over a 2x2 mesh
# ---------------------------------------------------------------------------

# bf16 keeps an 8-bit significand.  Both paths form the same per-token
# products, but the expert matmuls see differently shaped buffers ([32, 4 x
# cap, d] per chip against [128, cap, d]), so float32 partial sums may
# accumulate in another tile order before each bf16 rounding of the hidden
# activations; one such rounding (2^-8) carried through the d_ff sum bounds
# the difference at a few bf16 steps of the output's scale.
MOE_TOLERANCE = 2.0 ** -6          # of max |y| over the one-chip output


def moe_params(cfg, seed: int):
    """Random router and expert weights (distinct per expert), from ``seed``."""
    import jax
    import jax.numpy as jnp

    d, f, e = cfg.d_model, cfg.moe.d_ff_expert, cfg.moe.num_experts
    kr, kg, ku, kd = jax.random.split(jax.random.key(seed), 4)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(cfg.dtype)
    return {"router": w(kr, (d, e), d),
            "experts": {"w_gate": w(kg, (e, d, f), d),
                        "w_up": w(ku, (e, d, f), d),
                        "w_down": w(kd, (e, f, d), f)}}


def tokens(cfg, seed: int, batch: int, seq: int):
    """Random hidden states with a small component all tokens share, as a
    model's hidden states have: routing then favours some experts, so the
    hottest overflow their capacity and the dropped counts compared below
    are not 0."""
    import jax

    kx, ks = jax.random.split(jax.random.key(seed + 1))
    x = (jax.random.normal(kx, (batch, seq, cfg.d_model))
         + 0.1 * jax.random.normal(ks, (cfg.d_model,)))
    return x.astype(cfg.dtype)


def four_chip_phase(seed: int, cfg=None, batch: int = 8,
                    seq: int = 2048) -> list[str]:
    """One MoE FFN layer expert-parallel over ("pod", "model") = 2x2 with the
    two-level dispatch, against the same layer on one chip: each chip's
    token slice is routed there with that slice's expert capacity, exactly
    as the expert-parallel layer routes it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.qwen3_moe_235b_a22b import CONFIG
    from repro.launch.mesh import make_mesh
    from repro.models.moe import moe_ffn

    failures: list[str] = []
    devices = jax.devices()
    if len(devices) != 4:
        return [f"the four-chip phase needs 4 devices, found {len(devices)}"]
    cfg = dataclasses.replace(CONFIG if cfg is None else cfg, n_layers=1)
    m = cfg.moe
    ep_axes = ("pod", "model")
    mesh = make_mesh((2, 2), ep_axes)
    expert_spec = NamedSharding(mesh, P(ep_axes, None, None))
    shardings = {"router": NamedSharding(mesh, P()),
                 "experts": {k: expert_spec
                             for k in ("w_gate", "w_up", "w_down")}}
    t = time.perf_counter()
    params = jax.jit(lambda: moe_params(cfg, seed), out_shardings=shardings)()
    x = jax.jit(lambda: tokens(cfg, seed, batch, seq),
                out_shardings=NamedSharding(mesh, P("pod", None, None)))()
    jax.block_until_ready((params, x))
    spread = {}
    for name, leaf in params["experts"].items():
        shards = leaf.addressable_shards
        spread[name] = sorted(s.device.id for s in shards)
        if ({s.device for s in shards} != set(devices)
                or any(s.data.shape[0] != m.num_experts // 4 for s in shards)):
            failures.append(f"experts.{name} not spread over 4 devices: "
                            f"{[(s.device.id, s.data.shape) for s in shards]}")
    expert_bytes = sum(int(v.nbytes) for v in params["experts"].values())
    _log(phase="moe_setup", d_model=cfg.d_model, experts=m.num_experts,
         top_k=m.top_k, d_ff_expert=m.d_ff_expert, dtype=cfg.dtype,
         tokens=batch * seq, expert_bytes=expert_bytes,
         expert_devices=spread, seconds=time.perf_counter() - t)

    with jax.set_mesh(mesh):
        ep = jax.jit(lambda p, x: moe_ffn(p, cfg, x, mesh_axes=ep_axes))
        t = time.perf_counter()
        y_ep, _, drop_ep = jax.block_until_ready(ep(params, x))
        first = time.perf_counter() - t
        t = time.perf_counter()
        y_ep, _, drop_ep = jax.block_until_ready(ep(params, x))
        steady = time.perf_counter() - t
    _log(phase="moe_expert_parallel", chips=4, first_call_seconds=first,
         steady_seconds=steady, dropped=int(drop_ep))

    one = SingleDeviceSharding(devices[0])
    p1, x1 = jax.device_put((params, x), one)
    local = jax.jit(lambda p, xs: moe_ffn(p, cfg, xs, mesh_axes=()))
    tl = batch * seq // 4                 # tokens each chip routes
    x_slices = x1.reshape(4, 1, tl, cfg.d_model)
    t = time.perf_counter()
    outs = [local(p1, x_slices[i]) for i in range(4)]
    y_ref = jnp.concatenate([o[0] for o in outs]).reshape(y_ep.shape)
    drop_ref = sum(int(o[2]) for o in outs)
    one_chip_s = time.perf_counter() - t

    y_ep32 = np.asarray(jax.device_put(y_ep, one), np.float32)
    y_ref32 = np.asarray(y_ref, np.float32)
    scale = float(np.max(np.abs(y_ref32)))
    err = float(np.max(np.abs(y_ep32 - y_ref32)))
    finite = bool(np.isfinite(y_ep32).all())
    if not finite or err > MOE_TOLERANCE * scale:
        failures.append(f"MoE outputs differ: max|dy|={err} > "
                        f"{MOE_TOLERANCE} x max|y|={scale}")
    if int(drop_ep) != drop_ref:
        failures.append(f"dropped assignments differ: {int(drop_ep)} "
                        f"expert-parallel vs {drop_ref} on one chip")
    _log(phase="moe_one_chip", chips=1, seconds_with_compile=one_chip_s,
         dropped=drop_ref, max_abs_diff=err, max_abs_y=scale,
         tolerance=MOE_TOLERANCE * scale, finite=finite)
    _log_device_memory(devices)
    return failures


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the expert-parallel MoE phase on 4 chips")
    ap.add_argument("--wire", action="store_true",
                    help="run only the float64 wire-format phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    import repro.core  # noqa: F401  (fail before any output without the repo)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device})", file=sys.stderr)
        return 2
    _log(phase="device", compile_cache=use_compile_cache(), **device)
    if args.four_chip:
        failures = four_chip_phase(args.seed)
    elif args.wire:
        failures = wire_phase(args.seed)
    else:
        failures = (served_phase(SCALE_FACTOR, BATCH_SCALE_FACTOR, args.seed)
                    + wire_phase(args.seed))
    for f in failures:
        print(f"chip_smoke FAILED: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
