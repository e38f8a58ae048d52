"""The grouped sum's work, counted from the cell's data and queries alone,
and the chip's peaks it is held against.

Each row that enters an integer sum counts 8 B of value and 4 B of group
id, and each group of each input partition counts 8 B written. Nothing
of how the program pads, chunks or places the sum enters the count, so
the same data and query give the same bytes whatever implements them.
Input partitions are the engine's byte ranges of the CSV object: range
``i`` of ``n`` covers ``(i * step, (i + 1) * step]`` with ``step =
ceil(size / n)``, and a line belongs to the range its first byte lies in
(the first line to range 0).
"""

from __future__ import annotations

import json
import os

import numpy as np

VALUE_BYTES = 8
ID_BYTES = 4
OUT_BYTES = 8
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def partition_of_rows(data: bytes, nparts: int) -> np.ndarray:
    """Input partition of each line of ``data``."""
    buf = np.frombuffer(data, dtype=np.uint8)
    ends = np.flatnonzero(buf == ord("\n"))
    starts = np.concatenate([[0], ends[:-1] + 1])
    step = -(-len(buf) // nparts)
    part = np.maximum(0, -(-starts // step) - 1)
    return np.minimum(part, nparts - 1)


def _sum_specs(spec):
    """The aggregation specs of ``spec`` with the integer sums in each."""
    for s in spec.get("join", [spec]):
        n = sum(1 for a in s["aggs"] if a["op"] == "sum")
        if n:
            yield s, n


def grouped_sum_bytes(evaluator, rows, parts: np.ndarray, spec) -> int:
    """Least bytes the device grouped sum moves for one run of ``spec``;
    ``evaluator`` is the table's ``bench.reference.Evaluator``."""
    total = 0
    for s, n_sums in _sum_specs(spec):
        keep = evaluator.row_filter(s)
        keys = [evaluator.key_fn(k) for k in s["keys"]]
        entered = 0
        groups: set = set()
        for r, p in zip(rows, parts.tolist()):
            if keep(r):
                entered += 1
                groups.add((p,) + tuple(f(r) for f in keys))
        total += n_sums * (entered * (VALUE_BYTES + ID_BYTES)
                           + len(groups) * OUT_BYTES)
    return total
