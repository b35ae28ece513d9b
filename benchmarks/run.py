"""Benchmark aggregator: one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Suites: sampling (Fig 5/6), templates (Table 3), adaptive (Table 4),
failures (§5.2), moe_shuffle (beyond-paper LM integration).

NOTE: moe_shuffle needs >=8 devices in this process and reports 'skipped'
rows with fewer; it never starts a child process, because a parent that has
touched JAX holds the chip.  On the CPU, launch the whole run with
XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise it.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single suite by name")
    args = ap.parse_args()

    from . import (bench_adaptive, bench_failures, bench_moe_shuffle,
                   bench_sampling, bench_templates)
    suites = {
        "templates": bench_templates.run,
        "sampling": bench_sampling.run,
        "adaptive": bench_adaptive.run,
        "failures": bench_failures.run,
        "moe_shuffle": bench_moe_shuffle.run,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    t00 = time.time()
    for name, fn in suites.items():
        t0 = time.time()
        print(f"\n##### suite: {name}", flush=True)
        try:
            for table in fn():
                table.emit()
        except Exception as e:                      # pragma: no cover
            print(f"suite {name} FAILED: {e}", file=sys.stderr)
            raise
        print(f"# suite {name} took {time.time()-t0:.1f}s", flush=True)
    print(f"\n# all suites done in {time.time()-t00:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
