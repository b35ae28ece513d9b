"""The on-chip benchmark of the shuffle service: one cell per run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; both, the traffic's driver and operation, and every metric are
found by name (``spec.py``).  Set-up makes a pool of tables from
``--seed``; the driver (``drivers/<name>.py``) then sets up its clients of
``TeShuCluster`` -> ``tenant()`` -> ``shuffle()`` and warms the cell's
shapes.  The driver's window follows; nothing may compile inside it.  Once
it has closed, the shuffles it completed (all, or a share drawn from the
seed) are compared with the plain reference (``reference.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs a
shorter window under the profiler and reports the per-layer metrics.  The
last line of stdout is one JSON object; the numbers compared, each with its
limit, are the last lines of stderr and the result's last key.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

STARTED = time.monotonic()          # set-up counts from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from chipbench import reference, spec, work  # noqa: E402
from chipbench import trace as trace_reduction  # noqa: E402

WINDOW = "chipbench.window"
CALL = "chipbench.shuffle"
# The traced run's window: at most this long, and at most the traffic's
# "trace_calls" calls.  The profiler records every step of a COMB fold's
# loop (millions of events), and stopping it takes about two minutes per
# traced second of fold on a v5e host, so a cell whose calls fold stops it
# after its "trace_seconds".
TRACE_SECONDS = 10.0
SPAN_CAPACITY = 1 << 17


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, every program kept: only a checkout's first
    run of a cell compiles."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
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


class Table:
    """One generated table, spread evenly over the workers in row order (a
    table scan split into equal partitions)."""

    def __init__(self, keys, vals, workers, msgs_type):
        self.keys, self.vals = keys, vals
        parts = np.array_split(np.arange(keys.size), len(workers))
        self.bufs = {w: msgs_type(keys[p], vals[p]) for w, p in zip(workers, parts)}


def make_pool(cell: spec.Cell, seed: int, workers, msgs_type) -> list:
    gen = spec.generator(cell.config, cell.here)
    tr = cell.traffic
    pool = []
    for i in range(tr["pool"]):
        cols = gen.table(cell.config, tr["table"], seed, i)
        keys = cols[tr["key"]]
        vals = np.stack([cols[c] for c in tr["values"]], axis=1)
        if tr["placement"] == "shuffled":
            # rows stored apart from their key (as a table kept partitioned
            # on another column is): no key starts where it must end
            order = np.random.default_rng([seed % 2**63, i, 2]).permutation(keys.size)
            keys, vals = keys[order], vals[order]
        pool.append(Table(keys, vals, workers, msgs_type))
    return pool


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Session:
    """What a driver (``drivers/<name>.py``) works with: the cell's cluster,
    its pool of tables, and the record of every shuffle the window
    completes.

    ``record`` keeps for the comparison after the window the first shuffle
    and a share of the others (the traffic's ``check_share``, 1 by default),
    drawn from the seed; the rest it reads for their engine and plan and
    drops, so that the window's host memory does not grow with its calls."""

    CALL = CALL
    annotate = staticmethod(annotate)

    def __init__(self, cell: spec.Cell, seed: int, traced: bool):
        from repro.core import COMBINERS, Msgs, TeShuCluster, datacenter

        self.traffic = tr = cell.traffic
        lay = cell.config["cluster"]
        topo = datacenter(lay["workers_per_server"], lay["servers_per_rack"],
                          lay["racks"], oversubscription=lay["oversubscription"])
        self.workers = list(range(topo.num_workers))
        with annotate("chipbench.setup.data"):
            self.pool = make_pool(cell, seed, self.workers, Msgs)
        self.cluster = TeShuCluster(topo, tracing=traced,
                                    span_capacity=SPAN_CAPACITY)
        self.kwargs = {"comb_fn": COMBINERS[tr["combiner"]] if tr["combiner"] else None,
                       "balance": tr["balance"]}
        self._draw = np.random.default_rng([seed % 2**63, 3])
        self.calls = 0              # shuffles the window completed
        self.kept = []              # (call, table, result) compared afterwards
        self.raised = []            # (call, exception)
        self.off_jax = []           # calls on another engine, or fallen back
        self.not_rebalanced = []    # calls whose plan split no hot key

    def args(self, k: int) -> tuple:
        """The positional arguments of a shuffle of the pool's table ``k``
        (modulo its size)."""
        t = self.pool[k % len(self.pool)]
        return self.traffic["template"], dict(t.bufs), self.workers, self.workers

    def shuffle(self, client, k: int):
        return client.shuffle(*self.args(k), **self.kwargs)

    def record(self, k: int, res) -> None:
        """One shuffle of table ``k`` that the window completed: its
        ``ShuffleResult``, or the exception it raised."""
        i = self.calls
        self.calls += 1
        if isinstance(res, Exception):
            self.raised.append((i, res))
            return
        if res.engine != "jax" or res.fallback_reason is not None:
            self.off_jax.append(i)
        if self.traffic.get("rebalance") and not _rebalanced(res):
            self.not_rebalanced.append(i)
        if i == 0 or self._draw.random() < self.traffic.get("check_share", 1.0):
            self.kept.append((i, k % len(self.pool), res))


def _metric_values(entries, ctx, here: str) -> dict:
    out = {}
    for m in entries:
        v = spec.metric_reader(m["name"], here)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def traced_window(driver, seconds: float, max_calls, stop_after):
    """The driver's window under the profiler, and the trace's reduction.

    The trace runs from a start marker to an end marker.  With
    ``stop_after`` (the traffic's ``trace_seconds``) a timer ends it that
    many seconds into the window, inside a call whose every loop step the
    profiler would otherwise record; the call itself runs to its end."""
    import jax

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    lock, stopped = threading.Lock(), []

    def stop():
        with lock:
            if not stopped:
                with annotate(trace_reduction.end_marker(WINDOW)):
                    pass
                t_stop = time.monotonic()
                jax.profiler.stop_trace()
                stopped.append(t_stop)
                print(f"chipbench: profiler stopped in "
                      f"{time.monotonic() - t_stop:.1f} s", file=sys.stderr)
    try:
        jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
        with annotate(trace_reduction.start_marker(WINDOW)):
            pass
        timer = threading.Timer(stop_after, stop) if stop_after else None
        if timer:
            timer.start()
        try:
            calls, elapsed = driver.window(seconds, max_calls=max_calls)
        finally:
            if timer:
                timer.cancel()
            stop()
        cut = bool(calls) and stopped[0] < calls[-1][1]
        t_read = time.monotonic()
        trace = trace_reduction.reduce_dir(
            log_dir, WINDOW, CALL, default_label=CALL if cut else "between_calls")
        print(f"chipbench: trace ended {'inside' if cut else 'after'} the "
              f"window's last call; read and reduced in "
              f"{time.monotonic() - t_read:.1f} s", file=sys.stderr)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return calls, elapsed, trace


def check(s: Session, op: str, here: str) -> tuple[dict, int]:
    """Compare the kept shuffles with the plain reference; the numbers
    compared, each with its limit, and the count of calls at fault."""
    t_check = time.monotonic()
    refs = {}
    routing = reference.Routing()
    totals = dict.fromkeys(reference.LIMITS, 0)
    bad = {i for i, _ in s.raised} | set(s.off_jax) | set(s.not_rebalanced)
    for i, k, res in s.kept:
        if k not in refs:
            refs[k] = reference.expected(op, s.pool[k].keys, s.pool[k].vals, here)
        counts = reference.compare(res.bufs, refs[k], routing)
        for name, v in counts.items():
            totals[name] += v
        if any(counts.values()):
            bad.add(i)
    for _, exc in s.raised:
        print(f"chipbench: call failed: {type(exc).__name__}: {exc}",
              file=sys.stderr)
    print(f"chipbench: compared {len(s.kept)} of {s.calls} shuffles with the "
          f"reference in {time.monotonic() - t_check:.1f} s", file=sys.stderr)
    checks = {name: {"value": v, "limit": reference.LIMITS[name]}
              for name, v in totals.items()}
    checks["calls_raised"] = {"value": len(s.raised), "limit": 0}
    checks["calls_off_jax"] = {"value": len(s.off_jax), "limit": 0}
    if s.traffic.get("rebalance"):
        checks["calls_not_rebalanced"] = {"value": len(s.not_rebalanced),
                                          "limit": 0}
    return checks, len(bad)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices, peaks: dict | None, compiles: CompileCounter,
             started: float = STARTED) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    object (without its device block's platform fields)."""
    tr = cell.traffic
    s = Session(cell, seed, traced)
    driver = spec.driver(tr["driver"], cell.here).Driver(s)

    n_compiled = compiles.n
    setup_s = time.monotonic() - started
    trace = None
    if traced:
        calls, elapsed, trace = traced_window(
            driver, min(seconds, TRACE_SECONDS), tr.get("trace_calls"),
            tr.get("trace_seconds"))
    else:
        calls, elapsed = driver.window(seconds)
    compiled_in_window = compiles.n - n_compiled
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices) if devices else 0

    # ---- correctness, once the window has closed --------------------------
    checks, failed = check(s, tr["op"], cell.here)
    checks["compiles_in_window"] = {"value": compiled_in_window, "limit": 0}
    correct = bool(s.kept) and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    row_bytes = 8 + 8 * len(tr["values"])       # the wire format
    rows_in = int(s.pool[0].keys.size)
    rows_out = int(reference.expected(tr["op"], s.pool[0].keys, s.pool[0].vals,
                                      cell.here)[0].size)
    ctx = {
        "calls": calls, "elapsed": elapsed, "completed": len(calls),
        "setup_s": setup_s, "trace": trace, "peaks": peaks,
        "spans": s.cluster.obs.tracer.spans() if traced else [],
        "work": {"rows_in": rows_in, "rows_out": rows_out,
                 "min_hbm_bytes": work.min_hbm_bytes(rows_in, row_bytes,
                                                     rows_out, row_bytes)},
    }
    result = {
        "correct": correct, "attempted": s.calls, "failed": failed,
        "metrics": _metric_values(cell.per_layer if traced else cell.end_to_end,
                                  ctx, cell.here),
        "device": {"memory_peak_bytes": int(memory_peak)},
    }
    if traced:
        result["device"]["busy_s"] = trace["busy_s"] if trace else 0.0
        result["device"]["window_s"] = trace["window_s"] if trace else elapsed
        if trace:
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    return result


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python calls would swamp the host
    return opts


def _rebalanced(res) -> bool:
    """Whether the plan this call replayed split hot keys."""
    return any(isinstance(d, tuple) and len(d) == 2 and d[0] == "rebalance"
               and d[1] is not None and d[1].triggered for d in res.decisions)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(spec.load_benchmark(), args.workload)
    except (KeyError, OSError) as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2

    import jax

    import repro.core  # noqa: F401  (fail before any output without the repo)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks = work.peaks(dev.device_kind)
    except KeyError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], peaks, CompileCounter())
    checks = result.pop("checks")
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), **result["device"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
