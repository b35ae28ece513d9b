"""BENCHMARK.json against the benchmark's contract, and discovery by name:
a new cell, configuration and metric are new files and new entries only."""
import json
import os
import re
import shutil

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43_200
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.load(open(os.path.join(spec.ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        assert "assumed" in cfg and cfg["guarantees"]


def test_cells():
    configs = {c["name"] for c in BENCH["configs"]}
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = spec.resolve(BENCH, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in SOURCES
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert callable(spec.metric_reader(m["name"]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.resolve(BENCH, "no_such_cell")


ROUNDS_DRIVER = """
from chipbench.window import closed_loop

TENANTS = 2


class Driver:
    # two tenants each submit a shuffle, then one run_pending() per round
    def __init__(self, s):
        self.s = s
        self.clients = [s.cluster.tenant(f"t{j}") for j in range(TENANTS)]
        self.round(0)       # instantiates
        self.round(1)       # compiles the batched replay

    def round(self, r):
        ks = [r * TENANTS + j for j in range(TENANTS)]
        tickets = [c.submit(*self.s.args(k), **self.s.kwargs)
                   for c, k in zip(self.clients, ks)]
        out = self.s.cluster.run_pending()
        return [(k, out[t]) for k, t in zip(ks, tickets)]

    def window(self, seconds, max_calls=None):
        def call(i):
            for k, res in self.round(i + 2):
                self.s.record(k, res)
        calls, elapsed = closed_loop(call, seconds, max_calls=max_calls)
        return [c for c in calls for _ in self.clients], elapsed
"""

MAX_OP = """
import numpy as np


def expected(keys, vals):
    uniq, inv = np.unique(keys, return_inverse=True)
    out = np.full((uniq.size, vals.shape[1]), -np.inf)
    np.maximum.at(out, inv, vals)
    return uniq, out


def lowered(keys, vals):
    k, v = expected(keys, vals)
    return k, (v + 0.5).astype(np.float32).astype(np.float64)
"""


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path, monkeypatch):
    """Copy the benchmark, add a configuration, a traffic mix with a driver
    and an operation of its own, and a per-layer metric, as new files and
    new entries, and run the new cell (on the CPU, at a tiny size) with no
    harness file edited."""
    import repro.core.service as service
    from chipbench import reference, run

    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*"))
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.load(open(here / "configs" / "tpch_dc16.json"))
    cfg.update(name="tiny_tpch", rows={"lineitem": 3_000, "orders": 750,
                                       "part": 100, "customer": 75})
    (here / "configs" / "tiny_tpch.json").write_text(json.dumps(cfg))
    (here / "drivers" / "rounds.py").write_text(ROUNDS_DRIVER)
    (here / "ops" / "group_by_max.py").write_text(MAX_OP)
    (here / "workloads" / "q1_like.json").write_text(json.dumps({
        "driver": "rounds", "table": "lineitem", "key": "l_partkey",
        "values": ["l_quantity", "l_count"], "placement": "scan",
        "op": "group_by_max",
        "template": "vanilla_push", "combiner": "max", "balance": "off",
        "pool": 2}))
    (here / "metrics" / "calls_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['completed'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny_tpch", "source": "a test",
                             "file": "chipbench/configs/tiny_tpch.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny_q1", "config": "tiny_tpch",
                               "traffic": "q1_like", "chips": 1, "why": "a test"})
    rate = next(m for m in bench["end_to_end"] if m["name"] == "rows_per_s")
    rate["workloads"].append("tiny_q1")
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "a test", "moves": "rows_per_s",
                               "workloads": ["tiny_q1"]})
    bench["per_layer"].append({"name": "front_end_ms.tiny", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "a test", "moves": "rows_per_s",
                               "workloads": ["tiny_q1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve(spec.load_benchmark(str(tmp_path)), "tiny_q1",
                        root=str(tmp_path), here=str(here))
    assert cell.config["name"] == "tiny_tpch" and cell.traffic["pool"] == 2
    assert [m["name"] for m in cell.per_layer][-2:] == ["calls_seen",
                                                        "front_end_ms.tiny"]
    monkeypatch.setattr(service, "default_executor", lambda: "jax")
    res = run.run_cell(cell, 3, 0.2, True, [], None, run.CompileCounter())
    assert res["correct"] and res["attempted"] >= 2
    assert res["metrics"]["calls_seen"]["value"] == res["attempted"]
    assert res["metrics"]["front_end_ms.tiny"]["value"] > 0
    assert all(p.read_bytes() == b for p, b in before.items())

    # the new operation's control is refused by the comparison
    table = run.make_pool(cell, 4, list(range(16)), reference._Rows)[0]
    ref = reference.expected("group_by_max", table.keys, table.vals, str(here))
    got = reference.control_outputs("group_by_max", table.keys, table.vals, 16,
                                    str(here))
    assert reference.compare(got, ref)["values_wrong"] > 0
