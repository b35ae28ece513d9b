"""The plain reference against a brute-force loop, the comparison against
each fault it must count, and the float32 control it must refuse."""
import numpy as np
import pytest

from chipbench import reference, spec
from chipbench.reference import _Rows


def _table(seed, n=300, keys=40):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, keys, n).astype(np.int64) * 7 + 1
    v = rng.integers(0, 10_000, (n, 3)).astype(np.float64)
    return k, v


def _route(keys, vals, ndst=4):
    d = keys % ndst
    return {i: _Rows(keys[d == i], vals[d == i]) for i in range(ndst)}


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_by_sum_matches_a_loop(seed):
    k, v = _table(seed)
    sums = {}
    for key, row in zip(k.tolist(), v.tolist()):
        acc = sums.setdefault(key, [0.0] * len(row))
        for c, x in enumerate(row):
            acc[c] += x
    uniq, got = reference.expected("group_by_sum", k, v)
    assert uniq.tolist() == sorted(sums)
    assert got.tolist() == [sums[key] for key in sorted(sums)]


def test_partition_reference_is_every_row_sorted():
    k, v = _table(3)
    keys, vals = reference.expected("partition", k, v)
    rows = sorted(zip(k.tolist(), map(tuple, v.tolist())))
    assert list(zip(keys.tolist(), map(tuple, vals.tolist()))) == rows


@pytest.mark.parametrize("op", ["group_by_sum", "partition"])
@pytest.mark.parametrize("fault", ["none", "split", "lost", "extra", "altered"])
def test_compare_counts_each_fault(op, fault):
    k, v = _table(4)
    ref = reference.expected(op, k, v)
    keys, vals = ref[0], ref[1].copy()
    out = _route(keys, vals)
    want = dict.fromkeys(reference.LIMITS, 0)
    if fault == "split":              # a key's rows on two destinations
        key = keys[0]
        home = int(key % 4)
        rows = out[home].keys == key
        half = out[home].vals[rows][:1] / 2
        out[home] = _Rows(np.append(out[home].keys[~rows], key),
                          np.vstack([out[home].vals[~rows], half]))
        other = (home + 1) % 4
        out[other] = _Rows(np.append(out[other].keys, key),
                           np.vstack([out[other].vals, half]))
        want["keys_split"] = 1
        n_ref = int((keys == key).sum())
        want["rows_lost" if n_ref > 2 else "rows_extra"] = abs(n_ref - 2)
    elif fault == "lost":
        out[1] = _Rows(out[1].keys[1:], out[1].vals[1:])
        want["rows_lost"] = 1
    elif fault == "extra":
        out[2] = _Rows(np.append(out[2].keys, 10**9 + 2),
                       np.vstack([out[2].vals, vals[:1]]))
        want["rows_extra"] = 1
    elif fault == "altered":
        out[3].vals = out[3].vals.copy()
        out[3].vals[0, 1] += 1.0
        want["values_wrong"] = 1
    assert reference.compare(out, ref) == want


def test_compare_is_bit_exact():
    k, v = _table(5)
    ref = reference.expected("group_by_sum", k, v)
    vals = ref[1].copy()
    vals[0, 0] = np.nextafter(vals[0, 0], np.inf)
    assert reference.compare(_route(ref[0], vals), ref)["values_wrong"] == 1


@pytest.mark.parametrize("cell", ["q18_agg_push", "q17_skew_agg_push",
                                  "q18_orders_part"])
def test_float32_control_is_refused(cell):
    """The control (the reference in float32 in the program's place) fails
    the comparison; the float64 reference, routed alike, passes it."""
    from chipbench import run
    from repro.core import Msgs

    c = spec.resolve(spec.load_benchmark(), cell)
    c.config["rows"] = {"lineitem": 6_000, "orders": 1_500, "part": 200,
                        "customer": 150}
    table = run.make_pool(c, 11, list(range(16)), Msgs)[0]
    op = c.traffic["op"]
    ref = reference.expected(op, table.keys, table.vals)
    control = reference.control_outputs(op, table.keys, table.vals, 16)
    counts = reference.compare(control, ref)
    assert counts["values_wrong"] > 0
    assert reference.compare(_route(*ref, ndst=16), ref) == dict.fromkeys(
        reference.LIMITS, 0)


def test_routing_counts_keys_that_change_destination():
    routing = reference.Routing()
    assert routing.moved(np.array([5, 5, 9]), np.array([1, 1, 2])) == 0
    assert routing.moved(np.array([5, 9, 12]), np.array([3, 2, 0])) == 1
    assert routing.moved(np.array([7, 12]), np.array([0, 0])) == 0
    k, v = _table(6)
    ref = reference.expected("partition", k, v)
    routing = reference.Routing()
    assert reference.compare(_route(*ref), ref, routing)["keys_moved"] == 0
    shifted = {(d + 1) % 4: m for d, m in _route(*ref).items()}
    assert reference.compare(shifted, ref, routing)["keys_moved"] == np.unique(k).size
