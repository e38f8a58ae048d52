"""The TPC-H LINEITEM generator against the specification's rules, the
plain Q1/Q6 reference on a table made by hand, the mix's byte count, and
Q1, Q6 and a global aggregate over the taxi table through the engine
against their references."""

import datetime
import decimal

import numpy as np
import pytest

from bench import harness, reference, tpch_reference as ref

DS = harness.dataset("tpch-lineitem")
MIX = harness.load_mix("q1-q6", DS)
Q1, Q6 = MIX["queries"]
C = ref.Columns(DS.SCHEMA)
D = decimal.Decimal
SEED = 2**32 + 17


def _day(text):
    return datetime.date.fromisoformat(text)


def test_generator_keeps_the_spec_rules():
    raw = DS.generate(5000, SEED)
    assert raw == DS.generate(5000, SEED) != DS.generate(5000, SEED + 1)
    rows = reference.parse(raw)
    assert len(rows) == 5000 and all(len(r) == 16 for r in rows)
    current = _day(DS.CURRENTDATE)
    last_ship = _day(DS.ENDDATE) - datetime.timedelta(days=151 - 121)
    orders: dict = {}
    for r in rows:
        key, part = int(r[C.l_orderkey]), int(r[C.l_partkey])
        assert (key - 1) % 32 < 8  # dbgen's sparse order keys
        orders.setdefault(key, []).append(int(r[C.l_linenumber]))
        qty = D(r[C.l_quantity])
        assert qty == int(qty) and 1 <= qty <= 50
        retail = D(90000 + (part // 10) % 20001 + 100 * (part % 1000)) / 100
        assert D(r[C.l_extendedprice]) == qty * retail
        assert D("0") <= D(r[C.l_discount]) <= D("0.10")
        assert D("0") <= D(r[C.l_tax]) <= D("0.08")
        ship, commit, receipt = (_day(r[i]) for i in (
            C.l_shipdate, C.l_commitdate, C.l_receiptdate))
        assert 1 <= (receipt - ship).days <= 30
        # ship = order + 1..121 and commit = order + 30..90
        assert -120 <= (commit - ship).days <= 89
        assert _day(DS.STARTDATE) < ship <= last_ship
        assert r[C.l_returnflag] in (("R", "A") if receipt <= current
                                     else ("N",))
        assert r[C.l_linestatus] == ("O" if ship > current else "F")
        assert r[C.l_shipinstruct] in DS.INSTRUCTIONS
        assert r[C.l_shipmode] in DS.MODES
        assert 10 <= len(r[C.l_comment]) <= 43
    for lines in orders.values():
        assert lines == list(range(1, len(lines) + 1)) and len(lines) <= 7
    # orders keep their lines together and keys rise
    keys = [int(r[C.l_orderkey]) for r in rows]
    assert keys == sorted(keys)


def _line(flag, status, ship, qty, price, disc, tax):
    r = [""] * len(DS.SCHEMA)
    r[C.l_returnflag], r[C.l_linestatus], r[C.l_shipdate] = flag, status, ship
    r[C.l_quantity], r[C.l_extendedprice] = qty, price
    r[C.l_discount], r[C.l_tax] = disc, tax
    return r


HAND = [
    _line("A", "F", "1994-03-01", "10", "100.00", "0.05", "0.02"),
    _line("A", "F", "1998-09-02", "30", "301.01", "0.07", "0.08"),
    _line("N", "O", "1998-09-03", "5", "50.00", "0.00", "0.00"),  # late
    _line("N", "O", "1994-12-31", "23", "23.23", "0.06", "0.01"),
    _line("R", "F", "1995-01-01", "24", "24.00", "0.06", "0.00"),
]


def test_q1_reference_by_hand():
    got = ref.q1(HAND, C)
    # A/F: 10 + 30; 100 + 301.01; 95 + 279.9393; 96.9 + 302.334444
    assert got == [
        ("A", "F", D(40), D("401.01"), D("374.9393"), D("399.234444"),
         D("20.000000"), D("200.505000"), D("0.060000"), 2),
        ("N", "O", D(23), D("23.23"), D("21.8362"), D("22.054562"),
         D("23.000000"), D("23.230000"), D("0.060000"), 1),
        ("R", "F", D(24), D("24.00"), D("22.5600"), D("22.560000"),
         D("24.000000"), D("24.000000"), D("0.060000"), 1)]


def test_avg_rounds_half_up_at_scale_plus_4():
    assert ref._avg(D("0.01"), 8) == D("0.001250")
    assert ref._avg(D("0.01"), 16) == D("0.000625")
    assert ref._avg(D("-0.01"), 16) == D("-0.000625")
    assert ref._avg(D("1"), 3) == D("0.333333")
    assert ref._avg(D("2"), 3) == D("0.666667")


def test_q6_reference_by_hand():
    # 1994-03-01 at 0.05 and 1994-12-31 at 0.06 pass: 1995 is out of
    # the year, 0.07 is in range but 1998 is not, quantity 24 is not
    # below 24
    assert ref.q6(HAND, C) == [(D("5.0000") + D("1.3938"),)]
    assert ref.q6(HAND[1:3], C) == [(None,)]


def test_sum_bytes_by_hand():
    parts = np.array([0, 1, 1, 1, 0])
    # Q1: 4 rows enter (one ships late), 5 sums of 8 B and a 4 B id each;
    # groups (0,A,F) (1,A,F) (1,N,O) (0,R,F), 5 sums of 8 B each
    assert Q1.sum_bytes(HAND, parts) == 4 * (5 * 8 + 4) + 4 * 5 * 8
    # Q6: two rows in partitions 0 and 1, one sum, one group in each
    assert Q6.sum_bytes(HAND, parts) == 2 * (8 + 4) + 2 * 8


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_q1_q6_through_the_engine_equal_the_reference(backend):
    """Both queries on seeded 5,000-line data through FlintContext, the
    jax backend interpreting the limb kernel on the CPU: the answers equal
    the plain reference's, type for type, and no chunk leaves the fused
    operator."""
    from repro.core import FlintConfig, FlintContext

    raw = DS.generate(5000, SEED)
    rows = reference.parse(raw)
    ctx = FlintContext(config=FlintConfig(concurrency=8,
                                          vector_backend=backend))
    ctx.upload(DS.TABLE, raw)
    for q in (Q1, Q6):
        got = q.build(lambda: ctx.read_csv(DS.TABLE, list(DS.SCHEMA),
                                           8)).collect()
        want = q.reference(rows)
        assert harness.typed(got) == harness.typed(want)
        dev = ctx.last_scheduler.device_stats
        assert dev["fused_chunks"] and not dev["fused_row_fallback_chunks"]
        if backend == "jax":
            assert dev["kernel_calls"] and not dev["x64_sums"]
    assert len(Q1.reference(rows)) == 4


def test_global_aggregate_over_the_taxi_table():
    """A global count and sum over the taxi table on the jax backend, as
    Table I's Q0 asks, equal the plain count and sum: one row, and the
    sum on the device."""
    from repro.core import FlintConfig, FlintContext
    from repro.sql import col, count_, sum_

    taxi = harness.dataset("tlc-yellow-2015")
    raw = taxi.generate(4000, SEED)
    rows = reference.parse(raw)
    ctx = FlintContext(config=FlintConfig(concurrency=8,
                                          vector_backend="jax"))
    ctx.upload(taxi.TABLE, raw)
    df = ctx.read_csv(taxi.TABLE, list(taxi.SCHEMA), 8)
    got = df.agg(count_().alias("n"),
                 sum_(col("passenger_count")).alias("riders")
                 ).collect()
    ix = [n for n, _ in taxi.SCHEMA].index("passenger_count")
    assert got == [(len(rows), sum(int(r[ix]) for r in rows))]
    assert ctx.last_scheduler.device_stats["kernel_calls"]
