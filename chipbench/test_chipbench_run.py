"""run.py off the chip: its refusals, and a whole run at a tiny size with
the timed path sound and then broken underneath, once for each fault a
cell can have (one chip, so no exchange between chips to leave out)."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run, spec

TINY = {"lineitem": 6_000, "orders": 1_500, "part": 200, "customer": 150}


def test_main_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "q18_agg_push", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_main_refuses_an_unknown_cell(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_refuses_with_only_the_benchmark_files(tmp_path):
    """A checkout of BENCHMARK.json and chipbench/ alone has no program."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "q18_agg_push",
         "--seed", "2", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""


def _tiny(name):
    cell = spec.resolve(spec.load_benchmark(), name)
    cell.config["rows"] = dict(TINY)
    return cell


def _run(name, seed=2**31 + 5, traced=False):
    return run.run_cell(_tiny(name), seed, 0.3, traced, [], None,
                        run.CompileCounter())


@pytest.fixture
def on_jax(monkeypatch):
    import repro.core.service as service
    monkeypatch.setattr(service, "default_executor", lambda: "jax")


@pytest.mark.parametrize("name", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_sound_run_is_correct(on_jax, name):
    res = _run(name)
    assert res["correct"] and res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert "setup_s" in res["metrics"]
    assert any(n.split(".")[0] == "rows_per_s" for n in res["metrics"])
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)


def _break(monkeypatch, fault):
    """Wrap the served call so the timed path has ``fault``."""
    from repro.core import Msgs
    from repro.core.service import TenantClient

    sound = TenantClient.shuffle

    def broken(self, template, bufs, srcs, dsts, **kw):
        if fault == "half_batch":     # half of each worker's rows left out
            bufs = {w: m.take(np.arange(m.n // 2)) for w, m in bufs.items()}
        res = sound(self, template, bufs, srcs, dsts, **kw)
        if fault == "state_unchanged":    # the step hands back its input
            return dataclasses.replace(res, bufs={w: m.copy() for w, m in bufs.items()})
        if fault == "answer_altered":     # one value changed where produced
            d = next(d for d, m in res.bufs.items() if m.n)
            vals = res.bufs[d].vals.copy()
            vals[0, 0] += 1.0
            res.bufs[d] = Msgs(res.bufs[d].keys, vals)
        return res
    monkeypatch.setattr(TenantClient, "shuffle", broken)


@pytest.mark.parametrize("name", ["q18_agg_push", "q18_orders_part"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_each_fault_makes_the_run_incorrect(on_jax, monkeypatch, name, fault):
    _break(monkeypatch, fault)
    res = _run(name)
    assert res["correct"] is False and res["failed"] >= 1


def test_a_run_off_the_jitted_executor_is_incorrect():
    res = _run("q18_agg_push")      # the CPU's default executor is numpy
    assert res["correct"] is False
    assert res["checks"]["calls_off_jax"]["value"] == res["attempted"]


def test_a_compile_inside_the_window_is_incorrect(on_jax, monkeypatch):
    """Tables of a new row count inside the window compile a new program."""
    real = run.make_pool

    def uneven(cell, seed, workers, msgs_type):
        pool = real(cell, seed, workers, msgs_type)
        t = pool[-1]
        keep = np.arange(t.keys.size - 5)
        pool[-1] = run.Table(t.keys[keep], t.vals[keep], workers, msgs_type)
        return pool
    monkeypatch.setattr(run, "make_pool", uneven)
    res = _run("q18_agg_push")
    assert res["checks"]["compiles_in_window"]["value"] > 0
    assert res["correct"] is False


def test_a_skew_run_that_does_not_rebalance_is_incorrect(on_jax):
    cell = _tiny("q17_skew_agg_push")
    cell.traffic["balance"] = "off"
    res = run.run_cell(cell, 9, 0.3, False, [], None, run.CompileCounter())
    assert res["checks"]["calls_not_rebalanced"]["value"] == res["attempted"]
    assert res["correct"] is False
