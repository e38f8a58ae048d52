"""Seeded TPC-H LINEITEM rows, as the specification's dbgen fills them.

The sixteen columns, their order, names and types are LINEITEM's (TPC-H
v3.0.1 §1.4.1): identifiers and integers as ``int``, the four decimals
as ``decimal(15,2)``, the three dates as ``date``, text as ``str``.
Values follow §4.2.3, drawn column-wise with numpy from ``seed``:

* orders: order dates uniform on 1992-01-01 ... 1998-12-31 less 151
  days; order keys sparse as dbgen's (the first 8 of every 32); 1-7
  lines an order, numbered from 1;
* ``l_partkey`` uniform on [1, SF * 200,000]; ``l_suppkey`` one of the
  part's four suppliers by the spec's formula over SF * 10,000;
* ``l_quantity`` 1-50; ``l_extendedprice`` = quantity x P_RETAILPRICE,
  with P_RETAILPRICE = (90000 + ((partkey / 10) mod 20001) + 100 x
  (partkey mod 1000)) / 100; ``l_discount`` 0.00-0.10, ``l_tax``
  0.00-0.08;
* ship date = order date + 1-121 days, commit date = order date +
  30-90, receipt date = ship date + 1-30;
* ``l_returnflag`` R or A where the receipt date is on or before
  CURRENTDATE (1995-06-17), else N; ``l_linestatus`` O where the ship
  date is after it, else F;
* ``l_shipinstruct`` and ``l_shipmode`` from the spec's lists; a 10-43
  character ``l_comment`` cut, as dbgen cuts it, from a seeded pool of
  text made of the spec's words.

Fields are written as dbgen writes them (a quantity as a whole number,
money with two decimals, dates as ``YYYY-MM-DD``), but separated by
commas, the engine's and the reference's field separator; no comment
holds a comma. The scale factor follows the row count: SF 1 is about
6,000,000 lines. The last order is cut where the rows run out.
"""

from __future__ import annotations

import datetime
import decimal

import numpy as np

from bench import csvtext as ct
from bench import reference

TABLE = "lineitem.csv"
MONEY = "decimal(15,2)"
#: (name, dtype) of each column, in LINEITEM's order
SCHEMA = (("l_orderkey", "int"), ("l_partkey", "int"), ("l_suppkey", "int"),
          ("l_linenumber", "int"), ("l_quantity", MONEY),
          ("l_extendedprice", MONEY), ("l_discount", MONEY),
          ("l_tax", MONEY), ("l_returnflag", "str"),
          ("l_linestatus", "str"), ("l_shipdate", "date"),
          ("l_commitdate", "date"), ("l_receiptdate", "date"),
          ("l_shipinstruct", "str"), ("l_shipmode", "str"),
          ("l_comment", "str"))
# the plain reading of a money and a date field, for the reference's
# per-column casts, which know int, float and str
reference.CASTS.setdefault(MONEY, decimal.Decimal)
reference.CASTS.setdefault("date", datetime.date.fromisoformat)
ROWS_PER_SF = 6_000_000
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000
STARTDATE, ENDDATE, CURRENTDATE = "1992-01-01", "1998-12-31", "1995-06-17"
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
#: words of the spec's text grammar (§4.2.2.10): nouns, verbs,
#: adjectives, adverbs and prepositions
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias' patterns forges braids "
    "hockey players frays warhorses dugouts notornis epitaphs pearls "
    "tithes waters orbits gifts sheaves depths sentiments decoys realms "
    "pains grouches escapades "
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder "
    "print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt "
    "furious sly careful blithe quick fluffy slow quiet ruthless thin "
    "close dogged daring brave stealthy permanent enticing idle busy "
    "regular final ironic even bold silent "
    "sometimes always never furiously slyly carefully blithely quickly "
    "fluffily slowly quietly ruthlessly thinly closely doggedly daringly "
    "bravely stealthily permanently enticingly idly busily regularly "
    "finally ironically evenly boldly silently "
    "about above across after against along among around at atop before "
    "behind beneath beside besides between beyond by despite during "
    "except for from inside into near of on outside over past since "
    "through throughout to toward under until up upon without with "
    "within").split()
COMMENT_LEN = (10, 43)
POOL_WORDS = 200_000


def _day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


def _dates(days) -> np.ndarray:
    """``YYYY-MM-DD`` text of day numbers since 1970-01-01."""
    text = np.datetime_as_string(np.asarray(days).astype("datetime64[D]"),
                                 unit="D").astype("S10")
    return np.frombuffer(text.tobytes(), dtype=np.uint8).reshape(-1, 10)


def _comments(rng, n) -> np.ndarray:
    """dbgen's comments: random cuts of a pool of the spec's words."""
    words = np.asarray(WORDS)
    pool = " ".join(words[rng.integers(0, len(words), POOL_WORDS)])
    buf = np.frombuffer(pool.encode(), dtype=np.uint8)
    lo, hi = COMMENT_LEN
    length = rng.integers(lo, hi + 1, n)
    start = rng.integers(0, buf.size - hi, n)
    out = buf.take(start[:, None] + np.arange(hi))
    out[np.arange(hi) >= length[:, None]] = 0
    return out


def _orders(rng, n_rows):
    """Per line: its order's index and its line number."""
    per = rng.integers(1, 8, n_rows // 4 + 8)
    while per.sum() < n_rows:
        per = np.concatenate([per, rng.integers(1, 8, n_rows // 4 + 8)])
    ends = np.cumsum(per)
    n_orders = int(np.searchsorted(ends, n_rows)) + 1
    per = per[:n_orders]
    order = np.repeat(np.arange(n_orders), per)[:n_rows]
    first = np.repeat(ends[:n_orders] - per, per)[:n_rows]
    return order, np.arange(n_rows) - first + 1, n_orders


def generate(n_rows: int, seed: int) -> bytes:
    """``n_rows`` CSV lines, newline-terminated, a pure function of seed."""
    rng = ct.rng_for(seed)
    n = n_rows
    sf = n / ROWS_PER_SF
    parts = max(1, round(sf * PARTS_PER_SF))
    suppliers = max(4, round(sf * SUPPLIERS_PER_SF))
    order, linenumber, n_orders = _orders(rng, n)
    orderkey = (order // 8) * 32 + order % 8 + 1
    orderdate = rng.integers(_day(STARTDATE), _day(ENDDATE) - 151 + 1,
                             n_orders)[order]
    partkey = rng.integers(1, parts + 1, n)
    corner = rng.integers(0, 4, n)
    suppkey = (partkey + corner * (suppliers // 4 + (partkey - 1)
                                   // suppliers)) % suppliers + 1
    quantity = rng.integers(1, 51, n)
    retail_cents = (90000 + (partkey // 10) % 20001
                    + 100 * (partkey % 1000))
    price_cents = quantity * retail_cents
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    ship = orderdate + rng.integers(1, 122, n)
    commit = orderdate + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    current = _day(CURRENTDATE)
    flag = np.where(receipt <= current, rng.integers(0, 2, n), 2)
    status = (ship > current).astype(int)

    def cents(c, int_width):
        return ct.fixed_point(c, int_width, 2)

    return ct.join([
        ct.integer(orderkey, 10), ct.integer(partkey, 8),
        ct.integer(suppkey, 6), ct.integer(linenumber, 1),
        ct.integer(quantity, 2), cents(price_cents, 6),
        cents(discount, 1), cents(tax, 1),
        ct.choice(flag, ("R", "A", "N")), ct.choice(status, ("F", "O")),
        _dates(ship), _dates(commit), _dates(receipt),
        ct.choice(rng.integers(0, len(INSTRUCTIONS), n), INSTRUCTIONS),
        ct.choice(rng.integers(0, len(MODES), n), MODES),
        _comments(rng, n),
    ])
