"""The plain reference of TPC-H Q1 and Q6 (TPC-H v3.0.1 §2.4.1, §2.4.6).

It imports nothing of the program: ``decimal.Decimal``, ``datetime.date``
and dicts over the LINEITEM rows of strings that ``bench.reference.parse``
gives. Money is exact: a decimal(15,2) field is ``Decimal`` of its text,
sums and products are exact, and an average is the exact quotient
rounded half up to the argument's scale plus 4, as Spark types
``avg(decimal(p,s))``. Answers are sorted tuples; an aggregate over no
rows is None (a count 0).
"""

from __future__ import annotations

import datetime
import decimal

#: exact sums and products of decimal(15,2) over a few million rows
_EXACT = decimal.Context(prec=60, rounding=decimal.ROUND_HALF_UP)
#: avg of a scale-2 decimal has scale 6
AVG_UNIT = decimal.Decimal("0.000001")
#: the validation parameters
Q1_DELTA = 90
Q6_DATE = datetime.date(1994, 1, 1)
Q6_DISCOUNT = decimal.Decimal("0.06")
Q6_QUANTITY = 24


class Columns:
    """Column positions of LINEITEM, from its (name, dtype) schema."""

    def __init__(self, schema):
        for i, (name, _) in enumerate(schema):
            setattr(self, name, i)


def _avg(total, count):
    return _EXACT.divide(total, decimal.Decimal(count)).quantize(
        AVG_UNIT, rounding=decimal.ROUND_HALF_UP, context=_EXACT)


def q1_rows(rows, c: Columns, delta: int = Q1_DELTA):
    """The rows Q1 aggregates: ship date on or before 1998-12-01 less
    ``delta`` days."""
    cutoff = datetime.date(1998, 12, 1) - datetime.timedelta(days=delta)
    return [r for r in rows
            if datetime.date.fromisoformat(r[c.l_shipdate]) <= cutoff]


def q1(rows, c: Columns, delta: int = Q1_DELTA) -> list:
    """Pricing summary report: per (return flag, line status), sum_qty,
    sum_base_price, sum_disc_price, sum_charge, avg_qty, avg_price,
    avg_disc, count_order."""
    one = decimal.Decimal(1)
    groups: dict = {}
    for r in q1_rows(rows, c, delta):
        qty = decimal.Decimal(r[c.l_quantity])
        price = decimal.Decimal(r[c.l_extendedprice])
        disc = decimal.Decimal(r[c.l_discount])
        tax = decimal.Decimal(r[c.l_tax])
        disc_price = _EXACT.multiply(price, _EXACT.subtract(one, disc))
        charge = _EXACT.multiply(disc_price, _EXACT.add(one, tax))
        acc = groups.setdefault((r[c.l_returnflag], r[c.l_linestatus]),
                                [decimal.Decimal(0)] * 5 + [0])
        for j, v in enumerate((qty, price, disc_price, charge, disc)):
            acc[j] = _EXACT.add(acc[j], v)
        acc[5] += 1
    out = []
    for key, (qty, price, disc_price, charge, disc, n) in groups.items():
        out.append(key + (qty, price, disc_price, charge, _avg(qty, n),
                          _avg(price, n), _avg(disc, n), n))
    return sorted(out)


def q6_filter(r, c: Columns, day=Q6_DATE, discount=Q6_DISCOUNT,
              quantity=Q6_QUANTITY) -> bool:
    """Q6's predicate: a year of ship dates from ``day``, a discount
    within 0.01 of ``discount`` (ends included), quantity below
    ``quantity``."""
    ship = datetime.date.fromisoformat(r[c.l_shipdate])
    disc = decimal.Decimal(r[c.l_discount])
    step = decimal.Decimal("0.01")
    return (day <= ship < day.replace(year=day.year + 1)
            and discount - step <= disc <= discount + step
            and decimal.Decimal(r[c.l_quantity]) < quantity)


def q6(rows, c: Columns) -> list:
    """Forecasting revenue change: sum(l_extendedprice * l_discount)."""
    revenue = None
    for r in rows:
        if q6_filter(r, c):
            v = _EXACT.multiply(decimal.Decimal(r[c.l_extendedprice]),
                                decimal.Decimal(r[c.l_discount]))
            revenue = v if revenue is None else _EXACT.add(revenue, v)
    return [(revenue,)]
