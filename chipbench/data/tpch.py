"""TPC-H tables for the shuffle cells, generated from a seed.

The distributions are dbgen's (TPC-H v3.0.1, clause 4.2.3): ``sf * 1.5 M``
orders with 1-7 lines each, sparse order keys (8 kept of every 32), part
keys uniform over ``sf * 200,000`` parts, quantity 1-50, discount
0.00-0.10, tax 0.00-0.08, the part's retail price from its key, customer
keys uniform over ``sf * 150,000`` with every third customer left out, and
order dates uniform over 1992-01-01 .. 1998-08-02.

DECIMAL columns are carried as float64 hundredths, so every sum is an exact
integer whatever order it folds in, as TPC-H's decimal arithmetic is.

A configuration may skew ``l_partkey`` (``{"dist": "zipf", "z": ...}``), as
the skewed TPC-D/H generator of Chaudhuri and Narasayya does: part rank r
(part key r) takes a share proportional to 1/r**z.  The count of each part
key is fixed by the configuration, and the seed only decides which rows
carry it, so every seed gives the same hot keys with the same weights.
"""
from __future__ import annotations

import numpy as np

ORDER_DATE_DAYS = 2405          # 1992-01-01 .. 1998-08-02 (ENDDATE - 151 days)
CUSTOMER_MORTALITY = 3          # dbgen: custkey % 3 == 0 places no orders


def _rng(seed: int, table: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, table, stream])


def _lines_per_order(cfg: dict, rng) -> np.ndarray:
    """1-7 lines per order, nudged (one line at a time, on random orders) to
    the configuration's exact ``lineitem`` row count, as dbgen's is."""
    rows = cfg["rows"]
    lines = rng.integers(1, 8, rows["orders"])
    diff = rows["lineitem"] - int(lines.sum())
    if diff:
        step = 1 if diff > 0 else -1
        room = np.nonzero(lines < 7 if step > 0 else lines > 1)[0]
        lines[rng.choice(room, abs(diff), replace=False)] += step
    return lines


def order_keys(n_orders: int) -> np.ndarray:
    """dbgen's sparse order keys (mk_sparse): 1-8, 33-40, 65-72, ..."""
    idx = np.arange(1, n_orders + 1, dtype=np.int64)
    return ((idx >> 3) << 5) | (idx & 7)


def zipf_counts(n: int, domain: int, z: float) -> np.ndarray:
    """Rows per rank 1..domain for ``n`` rows under Zipf(z), by largest
    remainder, so the counts sum to ``n`` exactly."""
    w = 1.0 / np.arange(1, domain + 1, dtype=np.float64) ** z
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def _partkeys(cfg: dict, n: int, rng) -> np.ndarray:
    parts = cfg["rows"]["part"]
    dist = cfg["l_partkey"]
    if dist["dist"] == "uniform":
        return rng.integers(1, parts + 1, n)
    if dist["dist"] == "zipf":
        keys = np.repeat(np.arange(1, parts + 1, dtype=np.int64),
                         zipf_counts(n, parts, float(dist["z"])))
        return rng.permutation(keys)
    raise ValueError(f"unknown l_partkey distribution {dist['dist']!r}")


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """dbgen's p_retailprice, in hundredths."""
    return 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)


def lineitem(cfg: dict, seed: int, table: int = 0) -> dict[str, np.ndarray]:
    """The columns of ``lineitem`` the cells read, in generation order (an
    order's lines are adjacent), all in hundredths where DECIMAL."""
    rng = _rng(seed, table, 0)
    lines = _lines_per_order(cfg, rng)
    orderkey = np.repeat(order_keys(lines.size), lines)
    n = orderkey.size
    partkey = _partkeys(cfg, n, rng)
    quantity = rng.integers(1, 51, n)
    return {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_quantity": (quantity * 100).astype(np.float64),
        "l_extendedprice": (quantity * retail_cents(partkey)).astype(np.float64),
        "l_discount": rng.integers(0, 11, n).astype(np.float64),
        "l_tax": rng.integers(0, 9, n).astype(np.float64),
        "l_count": np.ones(n, np.float64),
    }


def orders(cfg: dict, seed: int, table: int = 0) -> dict[str, np.ndarray]:
    """The columns of ``orders`` the cells read.  ``o_totalprice`` sums the
    order's lines of :func:`lineitem` for the same seed and table, each line
    ``extendedprice * (1 + tax) * (1 - discount)`` rounded to the cent."""
    li = lineitem(cfg, seed, table)
    rng = _rng(seed, table, 1)
    n = cfg["rows"]["orders"]
    line_cents = np.rint(li["l_extendedprice"] * (100 + li["l_tax"])
                         * (100 - li["l_discount"]) / 10_000)
    _, first = np.unique(li["l_orderkey"], return_index=True)
    customers = cfg["rows"]["customer"]
    living = np.arange(1, customers + 1)
    living = living[living % CUSTOMER_MORTALITY != 0]
    return {
        "o_orderkey": order_keys(n),
        "o_custkey": rng.choice(living, n).astype(np.float64),
        "o_totalprice": np.add.reduceat(line_cents, first),
        "o_orderdate": rng.integers(0, ORDER_DATE_DAYS, n).astype(np.float64),
    }


TABLES = {"lineitem": lineitem, "orders": orders}


def table(cfg: dict, name: str, seed: int, index: int) -> dict[str, np.ndarray]:
    return TABLES[name](cfg, seed, index)
