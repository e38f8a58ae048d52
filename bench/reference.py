"""The plain reference: a traffic mix's query specs, evaluated with the
``csv`` module and Python dicts, on the same generated bytes.

It imports nothing of the program. A cell is read as its column's type
(``int()``, ``float()`` or the text), values are cast exactly as the
queries cast them (``int(value * scale)``), substrings are 1-based, and
answers are rows of (keys..., aggregates...), a join's rows being
(keys..., left aggregates..., right aggregates...), inner on the keys.
"""

from __future__ import annotations

import csv
import io

CASTS = {"int": int, "float": float, "str": str}


def parse(data: bytes) -> list:
    """The CSV as a list of rows of strings."""
    return list(csv.reader(io.StringIO(data.decode())))


class Evaluator:
    """Row functions of a spec's parts over a table of ``schema``."""

    def __init__(self, schema):
        self.index = {name: i for i, (name, _) in enumerate(schema)}
        self.cast = {name: CASTS[dtype] for name, dtype in schema}

    def column(self, name):
        i, cast = self.index[name], self.cast[name]
        return lambda r: cast(r[i])

    def key_fn(self, key):
        value = self.column(key["col"])
        if "substr" in key:
            lo = key["substr"][0] - 1
            hi = lo + key["substr"][1]
            return lambda r: value(r)[lo:hi]
        if key.get("cast") == "int":
            return lambda r: int(value(r))
        return value

    def value_fn(self, agg):
        if agg["op"] == "count":
            return lambda r: 1
        value = self.column(agg["col"])
        if "scale" in agg:
            scale = float(agg["scale"])
            return lambda r: int(value(r) * scale)
        return lambda r: int(value(r))

    def row_filter(self, spec):
        conds = [(self.column(c), v) for c, v in spec.get("where", ())]
        return lambda r: all(f(r) == v for f, v in conds)

    def grouped(self, rows, spec) -> dict:
        keep = self.row_filter(spec)
        keys = [self.key_fn(k) for k in spec["keys"]]
        vals = [self.value_fn(a) for a in spec["aggs"]]
        out: dict = {}
        for r in rows:
            if not keep(r):
                continue
            k = tuple(f(r) for f in keys)
            acc = out.get(k)
            if acc is None:
                out[k] = [f(r) for f in vals]
            else:
                for j, f in enumerate(vals):
                    acc[j] += f(r)
        return out

    def evaluate(self, rows, spec) -> list:
        """The answer to ``spec`` as sorted tuples."""
        if "join" in spec:
            left, right = (self.grouped(rows, s) for s in spec["join"])
            out = [k + tuple(left[k]) + tuple(right[k])
                   for k in left if k in right]
        else:
            out = [k + tuple(v) for k, v in self.grouped(rows, spec).items()]
        return sorted(out)
