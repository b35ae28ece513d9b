"""The TPC-H generators: row counts, dbgen's distributions, the Zipf skew,
and the skew split it trips in the served path."""
import numpy as np
import pytest

from chipbench import spec
from chipbench.data import tpch  # noqa: F401  (the module the configs name)

BENCH = spec.load_benchmark()


def _config(name):
    return spec.resolve(BENCH, next(w["name"] for w in BENCH["workloads"]
                                    if w["config"] == name)).config


@pytest.fixture(scope="module")
def gen():
    return spec.generator(_config("tpch_dc16"))


@pytest.mark.parametrize("config", ["tpch_dc16", "tpch_skew_z1_dc16"])
def test_lineitem_rows_and_columns(gen, config):
    cfg = _config(config)
    li = gen.lineitem(cfg, seed=2**31 + 3)
    assert li["l_orderkey"].size == cfg["rows"]["lineitem"] == 600_572
    orders, lines = np.unique(li["l_orderkey"], return_counts=True)
    assert orders.size == cfg["rows"]["orders"] == 150_000
    assert np.all((orders & 31) < 8) and orders[0] == 1       # sparse keys
    assert lines.min() == 1 and lines.max() == 7
    assert np.all(np.diff(li["l_orderkey"]) >= 0)   # an order's lines adjacent
    qty = li["l_quantity"] // 100
    assert set(np.unique(qty)) == set(range(1, 51))
    assert np.array_equal(li["l_extendedprice"],
                          qty * gen.retail_cents(li["l_partkey"]))
    assert li["l_discount"].min() == 0 and li["l_discount"].max() == 10
    assert li["l_tax"].min() == 0 and li["l_tax"].max() == 8
    assert li["l_partkey"].min() >= 1
    assert li["l_partkey"].max() <= cfg["rows"]["part"]


def test_orders_follow_lineitem(gen):
    cfg = _config("tpch_dc16")
    o = gen.orders(cfg, seed=5, table=2)
    li = gen.lineitem(cfg, seed=5, table=2)
    assert o["o_orderkey"].size == 150_000
    assert np.array_equal(o["o_orderkey"], np.unique(li["l_orderkey"]))
    assert np.all(o["o_custkey"] % 3 != 0)
    assert o["o_custkey"].max() <= cfg["rows"]["customer"]
    assert 0 <= o["o_orderdate"].min() and o["o_orderdate"].max() < 2405
    first = li["l_orderkey"] == o["o_orderkey"][0]
    line = np.rint(li["l_extendedprice"][first] * (100 + li["l_tax"][first])
                   * (100 - li["l_discount"][first]) / 10_000)
    assert o["o_totalprice"][0] == line.sum()


def test_seed_and_table_change_the_rows(gen):
    cfg = _config("tpch_dc16")
    a = gen.lineitem(cfg, seed=1, table=0)
    assert np.array_equal(a["l_partkey"], gen.lineitem(cfg, 1, 0)["l_partkey"])
    assert not np.array_equal(a["l_partkey"], gen.lineitem(cfg, 1, 1)["l_partkey"])
    assert not np.array_equal(a["l_partkey"], gen.lineitem(cfg, 2, 0)["l_partkey"])


def test_zipf_share_is_fixed_by_the_config(gen):
    cfg = _config("tpch_skew_z1_dc16")
    counts = [np.bincount(gen.lineitem(cfg, seed=s)["l_partkey"],
                          minlength=cfg["rows"]["part"] + 1)[1:]
              for s in (1, 2**31 + 9)]
    assert np.array_equal(counts[0], counts[1])
    share = counts[0] / counts[0].sum()
    harmonic = np.sum(1.0 / np.arange(1, 20_001))
    assert share[0] == pytest.approx(1 / harmonic, rel=1e-4)   # ~9.5%
    assert share[1] == pytest.approx(1 / (2 * harmonic), rel=1e-3)
    assert counts[0].min() >= 1


def test_skew_generator_trips_the_split_and_replays_on_jax(monkeypatch):
    """At the cell's size, balance="auto" splits five hot part keys and the
    second call replays the frozen plan on the jitted executor."""
    import repro.core.service as service
    from repro.core import SUM, Msgs, TeShuCluster, datacenter

    monkeypatch.setattr(service, "default_executor", lambda: "jax")
    cell = spec.resolve(BENCH, "q17_skew_agg_push")
    from chipbench import run
    topo = datacenter(4, 2, 2, oversubscription=4.0)
    workers = list(range(topo.num_workers))
    pool = run.make_pool(cell, 2**31 + 17, workers, Msgs)
    client = TeShuCluster(topo).tenant("client")
    first = client.shuffle("vanilla_push", pool[0].bufs, workers, workers,
                           comb_fn=SUM, balance="auto")
    dec = next(d[1] for d in first.decisions if d[0] == "rebalance")
    assert dec.triggered and len(dec.splits) == 5
    assert dec.top_share == pytest.approx(0.095, abs=0.001)
    assert dec.est_imbalance > 1.5
    second = client.shuffle("vanilla_push", pool[1].bufs, workers, workers,
                            comb_fn=SUM, balance="auto")
    assert (second.engine, second.cached, second.fallback_reason) == ("jax", True, None)
    assert run._rebalanced(second)
