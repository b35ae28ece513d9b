"""The chip's published peaks, and the least work a shuffle needs."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def min_hbm_bytes(rows_in: int, row_bytes_in: int, rows_out: int,
                  row_bytes_out: int) -> int:
    """Bytes any implementation of one shuffle must move through HBM: every
    input row read once and every output row written once, at the wire width
    (an 8-byte key plus 8 bytes per value column)."""
    return rows_in * row_bytes_in + rows_out * row_bytes_out
