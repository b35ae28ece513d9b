"""The plain reference and the comparison that decides ``correct``.

Written with numpy alone, apart from the system under test: it imports
nothing of ``repro`` and takes none of its outputs but the rows each
destination received.  Each operation a cell names (``group_by_sum``,
``partition``, ...) is ``ops/<op>.py``, found by name.

A correct shuffle puts every key on exactly one destination and delivers
exactly the reference's rows.  The values are float64 hundredths
whose sums stay below 2**53, so the comparison is exact: every limit is 0.
"""
from __future__ import annotations

import numpy as np

from chipbench import spec

# Each number compared, with its limit.  An exact comparison has the limit 0.
LIMITS = {"keys_split": 0, "keys_moved": 0, "rows_lost": 0, "rows_extra": 0,
          "values_wrong": 0}


def _sorted_rows(keys: np.ndarray, vals: np.ndarray):
    """Rows sorted by key, and by value within a key."""
    order = np.lexsort(tuple(vals.T[::-1]) + (keys,))
    return keys[order], vals[order]


def expected(op: str, keys: np.ndarray, vals: np.ndarray, here: str = spec.HERE):
    """The reference's rows for ``op`` over the whole input, sorted by key
    (then by value)."""
    return spec.op(op, here).expected(keys, vals)


class Routing:
    """Where each key has gone so far: a repartition sends a key to the same
    destination in every shuffle (what co-partitions the two sides of a
    join), whatever worker it started on."""

    def __init__(self):
        self.keys = np.empty(0, np.int64)
        self.dsts = np.empty(0, np.int64)

    def moved(self, keys: np.ndarray, dsts: np.ndarray) -> int:
        """Keys of this shuffle (sorted; a key's destination taken from its
        first row) that an earlier shuffle sent elsewhere; remembers the new
        ones."""
        first = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
        keys, dsts = keys[first], dsts[first]
        pos = np.minimum(np.searchsorted(self.keys, keys), max(self.keys.size - 1, 0))
        seen = (self.keys[pos] == keys) if self.keys.size else np.zeros(keys.size, bool)
        moved = int((self.dsts[pos][seen] != dsts[seen]).sum()) if seen.any() else 0
        if not seen.all():
            self.keys = np.concatenate([self.keys, keys[~seen]])
            self.dsts = np.concatenate([self.dsts, dsts[~seen]])
            order = np.argsort(self.keys, kind="stable")
            self.keys, self.dsts = self.keys[order], self.dsts[order]
        return moved


def compare(outputs: dict, ref, routing: Routing | None = None) -> dict[str, int]:
    """Count each way ``outputs`` (destination -> rows with ``.keys`` and
    ``.vals``) departs from the reference rows ``ref``:

    - ``keys_split``: keys found on more than one destination;
    - ``keys_moved``: keys whose destination differs from the one
      ``routing`` saw them go to in an earlier shuffle;
    - ``rows_lost`` / ``rows_extra``: rows of a key missing from, or beyond,
      the reference's count of that key;
    - ``values_wrong``: among keys delivered as often as the reference has
      them, rows whose values are not bit-identical to the reference's.
    """
    ref_keys, ref_vals = ref
    width = ref_vals.shape[1]
    dsts = list(outputs)
    keys = np.concatenate([np.asarray(outputs[d].keys) for d in dsts]
                          or [np.empty(0, np.int64)])
    vals = np.concatenate([np.asarray(outputs[d].vals).reshape(-1, width)
                           for d in dsts] or [np.empty((0, width))])
    dst = np.repeat(np.asarray(dsts, np.int64),
                    [np.asarray(outputs[d].keys).size for d in dsts])
    order = np.argsort(keys, kind="stable")
    keys, vals, dst = keys[order], vals[order], dst[order]
    same = keys[1:] == keys[:-1]
    keys_split = int(np.unique(keys[1:][same & (dst[1:] != dst[:-1])]).size)
    keys_moved = routing.moved(keys, dst) if routing is not None and keys.size else 0

    # a reference with one row per key needs no order among a key's rows
    ref_unique = not np.any(ref_keys[1:] == ref_keys[:-1])
    if not ref_unique:
        keys, vals = _sorted_rows(keys, vals)
    if keys.size == ref_keys.size and np.array_equal(keys, ref_keys):
        rows_lost = rows_extra = 0        # every key as often as the reference
        a, b = vals, ref_vals
    else:
        uo, co = np.unique(keys, return_counts=True)
        ur, cr = np.unique(ref_keys, return_counts=True)
        allk = np.union1d(uo, ur)
        n_out = np.zeros(allk.size, np.int64)
        n_ref = np.zeros(allk.size, np.int64)
        n_out[np.searchsorted(allk, uo)] = co
        n_ref[np.searchsorted(allk, ur)] = cr
        rows_lost = int(np.maximum(n_ref - n_out, 0).sum())
        rows_extra = int(np.maximum(n_out - n_ref, 0).sum())
        # rows of keys delivered as often as the reference has them line up
        # in both sorted arrays
        whole = allk[n_out == n_ref]
        a, b = vals[np.isin(keys, whole)], ref_vals[np.isin(ref_keys, whole)]
    values_wrong = int(np.any(a.view(np.int64) != b.view(np.int64), axis=1).sum()) \
        if a.size else 0
    return {"keys_split": keys_split, "keys_moved": keys_moved,
            "rows_lost": rows_lost, "rows_extra": rows_extra,
            "values_wrong": values_wrong}


class _Rows:
    def __init__(self, keys, vals):
        self.keys, self.vals = keys, vals


def control_outputs(op: str, keys: np.ndarray, vals: np.ndarray,
                    ndst: int, here: str = spec.HERE) -> dict:
    """The reference put in the program's place one precision below the
    configuration's float64: the operation's ``lowered`` form (float32 on
    the default JAX device), each key's rows sent to destination
    ``key % ndst``.  It is what a shuffle that carried its payload in
    float32 would deliver, and the comparison has to find it wrong."""
    k, v = spec.op(op, here).lowered(keys, vals)
    dst = k % ndst
    return {d: _Rows(k[dst == d], v[dst == d]) for d in range(ndst)}
