"""Queries of a traffic mix written as data, for any table.

A spec is plain data (see ``bench/traffic/*.json``):

    {"name": ...,
     "where": [[column, value], ...],            # equality filters, ANDed
     "keys": [{"name", "col", "substr": [start, length] | "cast": "int"}],
     "aggs": [{"name", "op": "sum", "col", "scale"} | {"name", "op": "count"}]}

or a join of two such specs, ``{"name", "join": [left, right], "on":
[key, ...]}``. A sum adds ``int(value * scale)`` (``int(value)`` without
a scale); a ``cast`` key is ``int(value)``.

``SpecQuery`` is the query protocol the harness drives; a mix written in
Python (``bench/traffic/<mix>.py``) hands it objects of its own with the
same members:

* ``name``;
* ``build(table)``: the program's DataFrame of the query, where
  ``table()`` returns a fresh DataFrame over the uploaded table;
* ``reference(rows)``: the answer, as sorted tuples, from the table's
  rows of strings with no code of the program;
* ``nkeys``: how many leading columns of an answer row are its keys;
* ``sum_bytes(rows, parts)``: the least bytes the device grouped sum
  moves for one run (``bench/work.py``), or None where it cannot say.
"""

from __future__ import annotations

from bench import work
from bench.reference import Evaluator


def _agg_frame(df, spec):
    from repro.sql import col, count_, lit, sum_

    for column, value in spec.get("where", ()):
        df = df.where(col(column) == lit(value))
    for key in spec["keys"]:
        if "substr" in key or "cast" in key or key["name"] != key["col"]:
            e = col(key["col"])
            if "substr" in key:
                e = e.substr(*key["substr"])
            if key.get("cast") == "int":
                e = e.cast("int")
            df = df.withColumn(key["name"], e)
    aggs = []
    for agg in spec["aggs"]:
        if agg["op"] == "count":
            aggs.append(count_().alias(agg["name"]))
        elif agg["op"] == "sum":
            value = col(agg["col"])
            if "scale" in agg:
                value = value * lit(float(agg["scale"]))
            df = df.withColumn(agg["name"] + "_v", value.cast("int"))
            aggs.append(sum_(col(agg["name"] + "_v")).alias(agg["name"]))
        else:
            raise ValueError(f"unknown aggregate op {agg['op']!r}")
    return df.groupBy(*[k["name"] for k in spec["keys"]]).agg(*aggs)


class SpecQuery:
    """A query spec over a table of ``schema``."""

    def __init__(self, spec: dict, schema):
        self.spec = spec
        self.name = spec["name"]
        self.nkeys = len(spec["on"] if "join" in spec else spec["keys"])
        self.evaluator = Evaluator(schema)

    def build(self, table):
        spec = self.spec
        if "join" in spec:
            left, right = spec["join"]
            return _agg_frame(table(), left).join(
                _agg_frame(table(), right), on=list(spec["on"]))
        return _agg_frame(table(), spec)

    def reference(self, rows) -> list:
        return self.evaluator.evaluate(rows, self.spec)

    def sum_bytes(self, rows, parts) -> int:
        return work.grouped_sum_bytes(self.evaluator, rows, parts, self.spec)
