"""chip_smoke.py off the chip: its checks, its data and its refusal.

The script's served phase runs here on the CPU at a tiny scale factor with
the jitted executor forced, so a change that breaks the served path or the
script fails here before it reaches a chip.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.core import Msgs  # noqa: E402


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_served_phase_passes_on_cpu(monkeypatch, capsys):
    import repro.core.service as service
    monkeypatch.setattr(service, "default_executor", lambda: "jax")
    assert chip_smoke.served_phase(0.002, 0.001, seed=3) == []
    recs = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    served = [r for r in recs if r["phase"] == "served"]
    assert len(served) == 2 * (2 + chip_smoke.STEADY_CALLS)
    assert all(r["correct"] for r in served)
    assert all(r["engine"] == "jax" and r["compiles"] == 0
               for r in served if r["call"].startswith("steady"))
    assert [r["batched"] for r in recs if "ticket" in r] == [True, True]


def _wire_phase_on_cpu(monkeypatch, capsys):
    import repro.core.service as service
    monkeypatch.setattr(service, "default_executor", lambda: "jax")
    monkeypatch.setattr(chip_smoke, "WIRE_VALUES", 3000)
    failures = chip_smoke.wire_phase(seed=4, rows=1500)
    recs = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    return failures, [r for r in recs if r["phase"] == "wire"]


def test_wire_phase_passes_on_cpu(monkeypatch, capsys):
    failures, recs = _wire_phase_on_cpu(monkeypatch, capsys)
    assert failures == []
    kinds = [r for r in recs if "kind" in r]
    assert [r["kind"] for r in kinds] == list(chip_smoke.wire_values(0))
    assert all(f["n"] == 0 for r in kinds for f in r["faults"].values())
    assert all(r["runtime_moved"] == 0 and not r["pairs"] for r in kinds)
    assert [r["template"] for r in recs if "template" in r] == list(
        chip_smoke.TEMPLATES)


def test_wire_phase_catches_a_split_unlike_the_runtimes(monkeypatch, capsys):
    """Forced onto the CPU, whose runtime moves float64 as it is, the TPU's
    float32 pairs round what the runtime does not: the phase reports the
    entry, the exit and the whole replays."""
    from repro.core import jaxplan
    monkeypatch.setattr(jaxplan, "_float_pairs", lambda: True)
    failures, _ = _wire_phase_on_cpu(monkeypatch, capsys)
    for what in ("general: the entry", "general: the exit",
                 "general: the both_ways", "hundredths: the entry",
                 "vanilla_push: outputs [1]", "network_aware: outputs [1]"):
        assert any(what in f for f in failures), what


def test_lineitem_follows_dbgen():
    keys, vals = chip_smoke.lineitem(0.01, seed=1)
    orders, lines = np.unique(keys, return_counts=True)
    assert orders.size == 15_000
    assert np.all((orders & 31) < 8) and orders[0] == 1       # sparse keys
    assert lines.min() == 1 and lines.max() == 7
    assert np.all(np.diff(keys) >= 0)           # an order's lines adjacent
    qty, price, disc, tax = vals.T
    assert set(np.unique(qty // 100)) == set(range(1, 51))
    assert np.all(price % (qty // 100) == 0)    # quantity x retail price
    assert disc.min() == 0 and disc.max() == 10
    assert tax.min() == 0 and tax.max() == 8


@pytest.mark.parametrize("fault", ["none", "sum", "dup", "lost"])
def test_check_outputs(fault):
    keys, vals = chip_smoke.lineitem(0.002, seed=2)
    ref = chip_smoke.group_by_sum(keys, vals)
    uniq, sums = ref[0].copy(), ref[1].copy()
    if fault == "sum":
        sums[5, 1] += 1.0
    out = {0: Msgs(uniq[::2], sums[::2]), 1: Msgs(uniq[1::2], sums[1::2])}
    if fault == "dup":
        out[1] = Msgs(np.append(out[1].keys, uniq[0]),
                      np.vstack([out[1].vals, sums[:1]]))
    if fault == "lost":
        out[1] = out[1].take(np.arange(1, out[1].n))
    err = chip_smoke.check_outputs(out, ref)
    expect = {"none": None, "sum": "sums", "dup": "more than one",
              "lost": "output rows"}[fault]
    if expect is None:
        assert err is None
    else:
        assert expect in err
