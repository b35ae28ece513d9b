"""The readings that the limits of ``reference.LIMITS`` were set from.

    python chipbench/readings.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

For each seed of ``--seeds``, one run of the cell as ``run.py`` makes it
(set-up, a window of ``--seconds``, the comparison), all in this one process
so that set-up compiles once.  For each seed of ``--control-seeds``, the
control: the plain reference computed one precision below the
configuration's float64 (float32, on the chip; ``reference.control_outputs``)
over every table of that seed's pool, put through the same comparison.
Prints one JSON line per reading, then the largest reading of the program
and the smallest of the control for each number compared.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from chipbench import reference, run, spec, work  # noqa: E402


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)

    import jax

    from repro.core import Msgs, datacenter

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    run.use_compile_cache()
    peaks = work.peaks(devices[0].device_kind)
    compiles = run.CompileCounter()
    program: dict[str, list] = {}
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, devices[:cell.chips],
                           peaks, compiles, started=time.monotonic())
        for name, c in res["checks"].items():
            program.setdefault(name, []).append(c["value"])
        print(json.dumps({"side": "program", "seed": seed,
                          "correct": res["correct"], "calls": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    control: dict[str, list] = {}
    lay = cell.config["cluster"]
    ndst = datacenter(lay["workers_per_server"], lay["servers_per_rack"],
                      lay["racks"], oversubscription=lay["oversubscription"]
                      ).num_workers
    for seed in args.control_seeds:
        totals = dict.fromkeys(reference.LIMITS, 0)
        for table in run.make_pool(cell, seed, list(range(ndst)), Msgs):
            ref = reference.expected(cell.traffic["op"], table.keys, table.vals)
            got = reference.control_outputs(cell.traffic["op"], table.keys,
                                            table.vals, ndst)
            for name, v in reference.compare(got, ref).items():
                totals[name] += v
        for name, v in totals.items():
            control.setdefault(name, []).append(v)
        print(json.dumps({"side": "control", "seed": seed,
                          "correct": not any(totals.values()),
                          "checks": totals}), flush=True)
    print(json.dumps({
        "cell": cell.name,
        "program_max": {k: max(v) for k, v in program.items()},
        "control_min": {k: min(v) for k, v in control.items()},
        "control_max": {k: max(v) for k, v in control.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
