"""Dates and exact decimals in the DataFrame engine: dtypes, literal
folding, Spark's scale rules, the row path against the vectorized path,
the CSV parse, the wire, global aggregates, and the device's limb sums
as the engine's counters record them."""

import datetime
import random
from decimal import Decimal

import numpy as np
import pytest

from repro.core import FlintConfig, FlintContext, serde
from repro.sql import avg_, col, count_, interval, lit, max_, min_, sum_
from repro.sql import csvscan
from repro.sql import vectorized as V
from repro.sql.expr import (Lit, Schema, canonical_dtype, cast_fn,
                            dtype_serde_char, parse_date, parse_decimal,
                            scan_cast)

D = datetime.date
MONEY = "decimal(15,2)"
SCHEMA = [("k", "int"), ("price", MONEY), ("disc", MONEY),
          ("ship", "date"), ("flag", "str")]


def _ctx(**kw):
    return FlintContext(config=FlintConfig(concurrency=4, **kw))


PATHS = {"row": dict(vectorize=False),
         "numpy": dict(vector_backend="numpy"),
         "jax": dict(vector_backend="jax")}


def _csv(n, seed, big=False):
    rng = random.Random(seed)
    lines = []
    for i in range(n):
        cents = rng.randint(-10**13, 10**13) if big else rng.randint(
            -10**7, 10**7)
        sign = "-" if cents < 0 else ""
        day = D(1992, 1, 1) + datetime.timedelta(days=rng.randint(0, 2500))
        lines.append(f"{i % 5},{sign}{abs(cents) // 100}."
                     f"{abs(cents) % 100:02d},0.{rng.randint(0, 10):02d},"
                     f"{day.isoformat()},{'AB'[i % 2]}")
    return ("\n".join(lines) + "\n").encode()


# ------------------------------------------------------------------ dtypes


@pytest.mark.parametrize("given,canon", [
    ("decimal(15,2)", "decimal(15,2)"), ("decimal( 15 , 2 )",
                                         "decimal(15,2)"),
    ("date", "date"), ("list:decimal(9,3)", "list:decimal(9,3)"),
    ("int", "int")])
def test_dtypes_parse_to_one_spelling(given, canon):
    assert canonical_dtype(given) == canon
    assert Schema([("x", given)]).fields == (("x", canon),)


@pytest.mark.parametrize("bad", ["decimal", "decimal(39,2)", "decimal(2,3)",
                                 "decimal(0,0)", "datetime", "list:list:int"])
def test_bad_dtypes_are_refused(bad):
    with pytest.raises(ValueError, match="dtype"):
        Schema([("x", bad)])


def test_wire_codes_carry_scale_and_date():
    assert dtype_serde_char(MONEY) == "d2"
    assert dtype_serde_char("date") == "D"
    assert Schema(SCHEMA).serde_tuple() == "t(i,d2,d2,D,s)"


# ------------------------------------------------------- literal folding


@pytest.mark.parametrize("expr,want", [
    (lit(D(1998, 12, 1)) - interval(90, "day"), D(1998, 9, 2)),
    (lit(D(1994, 1, 1)) + interval(1, "year"), D(1995, 1, 1)),
    (lit(D(2000, 1, 31)) + interval(1, "month"), D(2000, 2, 29)),
    (lit(D(2001, 3, 31)) - interval(1, "month"), D(2001, 2, 28)),
    (interval(3, "day") + lit(D(1999, 12, 30)), D(2000, 1, 2)),
    (lit(Decimal("0.06")) - lit(Decimal("0.01")), Decimal("0.05")),
    (lit(Decimal("1.5")) * lit(Decimal("0.25")), Decimal("0.375")),
    (lit(Decimal("0.06")) + 1, Decimal("1.06")),
])
def test_literal_dates_intervals_and_decimals_fold(expr, want):
    assert isinstance(expr, Lit) and expr.value == want
    assert type(expr.value) is type(want)


def test_folded_literal_shows_in_the_plan():
    ctx = _ctx()
    ctx.upload("t.csv", _csv(10, 1))
    df = ctx.read_csv("t.csv", SCHEMA, 2)
    q = df.where(col("ship") <= lit(D(1998, 12, 1)) - interval(90, "day"))
    assert "(ship <= date '1998-09-02')" in q.explain()


def test_interval_only_shifts_a_date():
    sch = Schema(SCHEMA)
    with pytest.raises(TypeError, match="interval"):
        (col("price") + interval(1, "day")).dtype(sch)
    with pytest.raises(ValueError, match="unit"):
        interval(1, "week")


# ----------------------------------------------------------- scale rules


@pytest.mark.parametrize("build,dtype", [
    (lambda: col("price") + col("disc"), "decimal(16,2)"),
    (lambda: col("price") - lit(Decimal("0.125")), "decimal(17,3)"),
    (lambda: col("price") * (1 - col("disc")), "decimal(38,4)"),
    (lambda: col("price") * col("disc"), "decimal(31,4)"),
    (lambda: col("price") * 3, "decimal(36,2)"),
    (lambda: col("price") / col("disc"), "float"),
    (lambda: col("price") < 24, "bool"),
    (lambda: col("ship") - col("ship"), "int"),
    (lambda: col("price").cast("decimal(10,4)"), "decimal(10,4)"),
])
def test_decimal_scale_rules(build, dtype):
    assert build().dtype(Schema(SCHEMA)) == dtype


@pytest.mark.parametrize("agg,dtype", [
    (sum_(col("price")), "decimal(25,2)"),
    (avg_(col("price")), "decimal(19,6)"),
    (avg_(col("k")), "float"),
    (count_(), "int"),
    (min_(col("ship")), "date"),
    (max_(col("disc")), MONEY),
])
def test_aggregate_dtypes(agg, dtype):
    assert agg.dtype(Schema(SCHEMA)) == dtype


@pytest.mark.parametrize("build", [
    lambda: col("price") + lit(0.5),
    lambda: col("price") < lit(0.06),
    lambda: col("price") % col("disc"),
    lambda: col("ship") + col("ship"),
    lambda: col("ship") < col("k"),
    lambda: sum_(col("ship")),
])
def test_decimal_never_meets_a_float_or_bad_op(build):
    with pytest.raises(TypeError):
        build().dtype(Schema(SCHEMA))


@pytest.mark.parametrize("src,to,value,want", [
    ("int", MONEY, 17, 1700),
    ("decimal(15,4)", MONEY, 12345, 123),        # 1.2345 -> 1.23
    ("decimal(15,4)", MONEY, 12350, 124),        # half up
    ("decimal(15,4)", MONEY, -12350, -124),      # half away from zero
    (MONEY, "int", -199, -1),                    # truncates
    (MONEY, "float", 2116823, 21168.23),
    (MONEY, "str", -5, "-0.05"),
    ("str", MONEY, "21168.23", 2116823),
    ("float", MONEY, 0.07, 7),
    ("str", "date", "1970-01-02", 1),
    ("date", "str", 10561, "1998-12-01"),
])
def test_casts(src, to, value, want):
    got = cast_fn(src, to)(value)
    assert got == want and type(got) is type(want)


# ----------------------------------------------------- row vs vectorized


def _rand_decimal_cols(rng, n):
    """int64 columns of unscaled decimals, negatives and values near
    the 2**62 guard included."""
    near = 2**62 // 10**2
    return [
        rng.integers(-10**9, 10**9, n),
        rng.integers(-10**4, 10**4, n),
        np.where(rng.random(n) < 0.05, rng.integers(near // 2, near, n),
                 rng.integers(-10**12, 10**12, n)),
    ]


EXPRS = [
    lambda: col("a") + col("b"),
    lambda: col("a") - col("c"),
    lambda: col("a") * col("b"),
    lambda: col("a") * (1 - col("b")) * (1 + col("b")),
    lambda: col("c") * col("c"),
    lambda: col("c") * 1000 + col("a"),
    lambda: col("a") / (col("b") * col("b") + 1),
    lambda: col("a") <= col("c"),
    lambda: col("b").between(lit(Decimal("-0.5")), lit(Decimal("7.25"))),
    lambda: col("a") == col("b") * 100,
    lambda: col("c").cast("decimal(30,0)"),
    lambda: col("a").cast("float"),
]


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("i", range(len(EXPRS)))
def test_row_and_vectorized_paths_are_bit_identical(i, seed):
    """Seeded random decimals, a few near the 2**62 guard: the array
    path's answer (or its fallback to the row path) equals the row
    closure's on every row."""
    sch = Schema([("a", "decimal(18,4)"), ("b", "decimal(10,2)"),
                  ("c", "decimal(18,2)")])
    rng = np.random.default_rng(seed)
    n = 500
    cols = _rand_decimal_cols(rng, n)
    expr = EXPRS[i]()
    rows = list(zip(*[c.tolist() for c in cols]))
    want = [expr.bind(sch)(r) for r in rows]
    try:
        got = V.to_list(V.compile_expr(expr, sch)(cols, n), n)
    except V.VectorFallback:
        return  # the fused operator re-runs the chunk on the row path
    assert got == want
    assert [type(v) for v in got] == [type(v) for v in want]


def test_dates_and_month_intervals_vectorize_exactly():
    sch = Schema([("d", "date")])
    rng = np.random.default_rng(3)
    days = rng.integers(-30000, 30000, 400)
    for expr in (col("d") + interval(1, "month"),
                 col("d") - interval(13, "month"),
                 col("d") + interval(2, "year"),
                 col("d") - interval(40, "day"),
                 col("d") > lit(D(1994, 1, 1))):
        want = [expr.bind(sch)((int(v),)) for v in days]
        assert V.to_list(V.compile_expr(expr, sch)([days], 400), 400) == want


# -------------------------------------------------------------- CSV parse


def test_columnar_and_line_parse_agree_on_decimals_and_dates():
    rng = random.Random(11)
    fields = ["21168.23", "17", "0.04", "-0.5", ".5", "5.", "1.234", "-0",
              "99999999999999.99", "12345678901234567.8", "1e3", " 7",
              "00012.10", "-.05"]
    days = ["1998-12-01", "1970-01-01", "2000-02-29", "0001-01-01",
            "9999-12-31", "19981201", "1996-03-13", "1969-12-31"]
    lines = []
    for _ in range(600):
        dec = (rng.choice(fields) if rng.random() < 0.3 else
               f"{rng.randint(-10**9, 10**9) / 100:.2f}")
        lines.append(f"{dec},{rng.choice(days)},x")
    specs = [(0, MONEY, scan_cast(MONEY)), (1, "date", scan_cast("date")),
             (2, "str", str)]
    by_line = csvscan.parse_lines(lines, specs)
    by_col = csvscan.parse_columnar(lines, specs, 3)
    assert [c if isinstance(c, list) else c.tolist() for c in by_col] == [
        c if isinstance(c, list) else c.tolist() for c in by_line]
    assert parse_decimal("21168.23", 2) == 2116823
    assert parse_decimal("17", 2) == 1700
    assert parse_date("1998-12-01") == 10561


@pytest.mark.parametrize("bad", ["1999-02-30", "abc"])
def test_bad_fields_raise_on_both_parses(bad):
    specs = [(0, "date", scan_cast("date"))]
    with pytest.raises(ValueError):
        csvscan.parse_lines([bad], specs)
    with pytest.raises(ValueError):
        csvscan.parse_columnar([bad], specs, 1)


# ------------------------------------------------------------------- wire


def test_decimal_and_date_codecs_round_trip():
    for schema, vals in (("d2", [1, -2**63, 2**63 - 1]), ("D", [0, -5, 10561]),
                         ("t(s,d6,D)", [("a", 10**15, 3)])):
        assert serde.column_conforms(schema, vals)
        blob = serde.encode_column(schema, vals)
        assert serde.decode_column(schema, blob, len(vals)) == vals
    assert not serde.column_conforms("D", [2**40])
    assert not serde.column_conforms("d2", [1.5])


@pytest.mark.parametrize("transport", ["sqs", "s3"])
@pytest.mark.parametrize("vectorize", [True, False])
def test_decimal_and_date_columns_cross_the_shuffle(transport, vectorize):
    """Full rows of decimals and dates ride a groupByKey and a join over
    either transport and come back exact."""
    data = _csv(400, 7)
    ctx = _ctx(shuffle_backend=transport, vectorize=vectorize)
    ctx.upload("t.csv", data)
    df = ctx.read_csv("t.csv", SCHEMA, 4)
    lists = dict(df.groupBy("k").agg(
        min_(col("price")).alias("lo"), max_(col("ship")).alias("hi"),
        count_().alias("n")).select("k", "lo").collect())
    joined = df.join(df.groupBy("k").agg(max_(col("disc")).alias("top")),
                     on="k").select("k", "price", "ship", "top").collect()
    rows = [ln.split(",") for ln in data.decode().splitlines()]
    for k in range(5):
        mine = [r for r in rows if int(r[0]) == k]
        assert lists[k] == min(Decimal(r[1]) for r in mine)
    assert len(joined) == 400
    top = {k: max(Decimal(r[2]) for r in rows if int(r[0]) == k)
           for k in range(5)}
    assert all(row[3] == top[row[0]] and type(row[2]) is D
               for row in joined)


# ----------------------------------------------------- global aggregates


@pytest.mark.parametrize("path", ["row", "numpy", "jax"])
@pytest.mark.parametrize("optimize", [True, False])
def test_global_aggregate_is_one_row(path, optimize):
    data = _csv(300, 5)
    ctx = _ctx(**PATHS[path])
    ctx.upload("t.csv", data)
    df = ctx.read_csv("t.csv", SCHEMA, 4)
    rows = [ln.split(",") for ln in data.decode().splitlines()]
    prices = [Decimal(r[1]) for r in rows]
    got = df.agg(sum_(col("price")).alias("s"), count_().alias("n"),
                 avg_(col("price")).alias("a")).collect(optimize=optimize)
    mean = (sum(prices) / len(prices)).quantize(Decimal("0.000001"),
                                                rounding="ROUND_HALF_UP")
    assert got == [(sum(prices), 300, mean)]
    assert df.groupBy().agg(count_().alias("n")).count() == 1


@pytest.mark.parametrize("path", ["row", "numpy", "jax"])
def test_global_aggregate_over_no_rows(path):
    ctx = _ctx(**PATHS[path])
    ctx.upload("t.csv", _csv(200, 6))
    df = ctx.read_csv("t.csv", SCHEMA, 4)
    got = (df.where(col("k") > 10)
           .agg(sum_(col("price")).alias("s"), count_().alias("n"),
                avg_(col("disc")).alias("a"), min_(col("ship")).alias("m"))
           .collect())
    assert got == [(None, 0, None, None)]
    # a filter above the global aggregate is not pushed below it
    assert (df.agg(count_().alias("n")).where(col("n") > 1000)
            .collect()) == []


# ------------------------------------------ the device sum, by counters


def _device_stats(ctx):
    return ctx.last_scheduler.device_stats


def test_one_program_per_chunk_sums_every_decimal_slot():
    """A chunk's five distinct sums (and the avg slots that repeat two of
    them) go to the device as one program; the answer equals the row
    path's."""
    data = _csv(3000, 9)

    def query(df):
        p = col("price")
        return df.groupBy("flag").agg(
            sum_(p).alias("s1"), sum_(p * (1 - col("disc"))).alias("s2"),
            sum_(p * (1 - col("disc")) * (1 + col("disc"))).alias("s3"),
            sum_(col("disc")).alias("s4"), sum_(col("k")).alias("s5"),
            avg_(p).alias("a1"), avg_(col("disc")).alias("a2"),
            count_().alias("n"))

    out = {}
    for path in ("row", "jax"):
        ctx = _ctx(**PATHS[path])
        ctx.upload("t.csv", data)
        out[path] = sorted(query(ctx.read_csv("t.csv", SCHEMA, 4)).collect())
        stats = _device_stats(ctx)
    assert out["row"] == out["jax"]
    chunks = stats["fused_chunks"]
    assert chunks == 4 and stats["fused_row_fallback_chunks"] == 0
    assert stats["kernel_calls"] == chunks and stats["x64_sums"] == 0
    assert stats["device_sum_columns"] == 5 * chunks
    assert stats["device_limb_columns"] >= stats["device_sum_columns"]


@pytest.mark.parametrize("case,path", [
    ("small", "kernel_calls"), ("limbs", "kernel_calls"),
    ("wide", "x64_sums"), ("over", "device_fallbacks")])
def test_engine_takes_the_path_each_size_needs(case, path):
    """The engine picks the device path from the magnitudes and group
    counts it sees: small sums in one limb, wide ones in several, more
    than 8,192 groups in the x64 sum, past 2**62 back on the host."""
    n = 9000 if case == "wide" else 400
    rng = random.Random(2)
    if case == "over":
        vals = [2**61] * 4 + [1] * (n - 4)
    elif case == "limbs":
        vals = [rng.randint(-2**45, 2**45) for _ in range(n)]
    else:
        vals = [rng.randint(-99, 99) for _ in range(n)]
    keys = list(range(n)) if case == "wide" else [i % 3 for i in range(n)]
    rows = list(zip(keys, vals))
    out = {}
    for vec in (False, True):
        ctx = _ctx(vector_backend="jax", vectorize=vec,
                   vector_batch_rows=16384)
        df = ctx.parallelize(rows, 1).toDF([("k", "int"), ("v", "int")])
        out[vec] = sorted(df.where(col("k") >= lit(0)).groupBy("k")
                          .agg(sum_(col("v")).alias("s")).collect())
        stats = _device_stats(ctx)
    assert out[True] == out[False]
    assert stats[path] == 1
    # 400 rows take 15-bit limbs: |v| < 2**45 is 3 of them, for each sign
    limbs = {"small": 1, "limbs": 6, "wide": 1, "over": 0}[case]
    assert stats["device_limb_columns"] == limbs


def test_row_fallback_chunks_are_counted():
    """A chunk whose int64 arithmetic could wrap re-runs on the row path,
    and the task counts it."""
    rows = [(1, 2**61), (2, 3)]
    ctx = _ctx(vector_backend="numpy")
    df = ctx.parallelize(rows, 1).toDF([("k", "int"), ("v", "int")])
    got = df.select(col("k"), (col("v") * 8).alias("w")).collect()
    assert sorted(got) == [(1, 2**64), (2, 24)]
    stats = _device_stats(ctx)
    assert stats["fused_chunks"] == 1
    assert stats["fused_row_fallback_chunks"] == 1


def test_rdd_rows_of_dates_and_decimals_surface_back():
    rows = [(1, Decimal("1.25"), D(2000, 1, 1)), (2, Decimal("-0.5"),
                                                  D(1969, 12, 31))]
    for vec in (False, True):
        ctx = _ctx(vector_backend="numpy", vectorize=vec)
        df = ctx.parallelize(rows, 2).toDF(
            [("k", "int"), ("m", "decimal(9,2)"), ("d", "date")])
        got = sorted(df.where(col("m") > lit(Decimal("-1"))).collect())
        assert got == rows
        assert [type(v) for v in got[0]] == [int, Decimal, D]
