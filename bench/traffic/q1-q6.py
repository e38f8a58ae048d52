"""TPC-H Q1 and Q6 over LINEITEM at the specification's validation
parameters (TPC-H v3.0.1 §2.4.1, §2.4.6): one closed-loop client
alternates them.

* Q1, the pricing summary report: ship dates up to 1998-12-01 less
  DELTA = 90 days (about 98.6% of the rows), 4 groups by return flag and
  line status, 8 aggregates (sums, averages and a count of exact
  decimals), ordered by the keys.
* Q6, the forecasting revenue change: ship dates in the year from DATE =
  1994-01-01, discounts within 0.01 of DISCOUNT = 0.06, quantities below
  QUANTITY = 24 (about 1.9% of the rows), and one global sum.

Dates, intervals and decimal literals are written as in the queries'
text and folded by the engine. ``sum_bytes`` counts from the data and
the queries alone: 8 B a row for each distinct sum a row enters, 4 B of
group id a row entering the aggregation, and 8 B for each sum of each
group of each input partition.
"""

from __future__ import annotations

import datetime
import decimal

from bench import tpch_reference as ref
from bench import work


def _sum_bytes(entered, key, n_sums: int) -> int:
    """``entered``: (row, partition) pairs entering the aggregation."""
    groups = {(p,) + key(r) for r, p in entered}
    return (len(entered) * (n_sums * work.VALUE_BYTES + work.ID_BYTES)
            + len(groups) * n_sums * work.OUT_BYTES)


class Q1:
    name = "q1"
    nkeys = 2
    #: distinct summed values: quantity, price, discounted price,
    #: charge, discount (avg_qty and avg_price reuse two of them)
    n_sums = 5

    def __init__(self, schema):
        self.c = ref.Columns(schema)

    def build(self, table):
        from repro.sql import avg_, col, count_, interval, lit, sum_

        price = col("l_extendedprice")
        disc_price = price * (1 - col("l_discount"))
        charge = disc_price * (1 + col("l_tax"))
        cutoff = (lit(datetime.date(1998, 12, 1))
                  - interval(ref.Q1_DELTA, "day"))
        return (table().where(col("l_shipdate") <= cutoff)
                .groupBy("l_returnflag", "l_linestatus")
                .agg(sum_(col("l_quantity")).alias("sum_qty"),
                     sum_(price).alias("sum_base_price"),
                     sum_(disc_price).alias("sum_disc_price"),
                     sum_(charge).alias("sum_charge"),
                     avg_(col("l_quantity")).alias("avg_qty"),
                     avg_(price).alias("avg_price"),
                     avg_(col("l_discount")).alias("avg_disc"),
                     count_().alias("count_order"))
                .orderBy("l_returnflag", "l_linestatus"))

    def reference(self, rows) -> list:
        return ref.q1(rows, self.c)

    def sum_bytes(self, rows, parts) -> int:
        keep = {id(r) for r in ref.q1_rows(rows, self.c)}
        entered = [(r, p) for r, p in zip(rows, parts.tolist())
                   if id(r) in keep]
        c = self.c
        return _sum_bytes(entered,
                          lambda r: (r[c.l_returnflag], r[c.l_linestatus]),
                          self.n_sums)


class Q6:
    name = "q6"
    nkeys = 0
    n_sums = 1

    def __init__(self, schema):
        self.c = ref.Columns(schema)

    def build(self, table):
        from repro.sql import col, interval, lit, sum_

        day = lit(ref.Q6_DATE)
        step = lit(decimal.Decimal("0.01"))
        discount = lit(ref.Q6_DISCOUNT)
        return (table()
                .where((col("l_shipdate") >= day)
                       & (col("l_shipdate") < day + interval(1, "year"))
                       & col("l_discount").between(discount - step,
                                                   discount + step)
                       & (col("l_quantity") < lit(ref.Q6_QUANTITY)))
                .agg(sum_(col("l_extendedprice")
                          * col("l_discount")).alias("revenue")))

    def reference(self, rows) -> list:
        return ref.q6(rows, self.c)

    def sum_bytes(self, rows, parts) -> int:
        entered = [(r, p) for r, p in zip(rows, parts.tolist())
                   if ref.q6_filter(r, self.c)]
        return _sum_bytes(entered, lambda r: (), self.n_sums)


def mix(dataset):
    return {"order": "alternate",
            "queries": [Q1(dataset.SCHEMA), Q6(dataset.SCHEMA)]}
