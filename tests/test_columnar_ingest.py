"""The scan's column-wise CSV parse (``repro.sql.csvscan.parse_columnar``,
behind ``sql/vectorized.py:scan_ingest``) against the per-line parse it
stands in for: every column equal bit for bit, the same exception type
where the per-line parse raises, the per-line parse for every chunk the
column-wise one refuses, and the chunk counters in the scheduler's
``device_stats``."""

import importlib.util
import math
import pathlib
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import FlintConfig, FlintContext
from repro.sql import Schema, col, count_, lit, sum_
from repro.sql import csvscan
from repro.sql import vectorized as V
from repro.sql.expr import CASTS

ROOT = pathlib.Path(__file__).resolve().parent.parent
MIXED = [("s", "str"), ("i", "int"), ("f", "float"), ("b", "bool"),
         ("t", "str"), ("g", "float")]


def _specs(schema, names=None):
    fields = [n for n, _ in schema]
    return [(fields.index(n), t, CASTS[t]) for n, t in schema
            if names is None or n in names]


def _tlc_lines(n, seed):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))  # the generator imports bench.csvtext
    spec = importlib.util.spec_from_file_location(
        "tlc_yellow_2015", ROOT / "bench" / "datasets" / "tlc-yellow-2015.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate(n, seed).decode().splitlines(), list(mod.SCHEMA)


def _outcome(parse):
    try:
        return parse()
    except Exception as e:  # the type is what the two paths must share
        return type(e)


def _assert_same(got, want):
    if isinstance(want, type):
        assert got is want
        return
    assert not isinstance(got, type), got
    assert len(got) == len(want)
    for a, b in zip(got, want):
        if isinstance(b, list):
            assert type(a) is list and a == b
            assert all(type(x) is type(y) for x, y in zip(a, b))
            continue
        assert a.dtype == b.dtype and a.shape == b.shape
        if b.dtype == np.float64:  # bits: -0.0 vs 0.0, every NaN payload
            assert (a.view(np.int64) == b.view(np.int64)).all()
        else:
            assert (a == b).all()


def _row(i="7", f="2.50", b="true", s="x", t="y", g="0.5"):
    return f"{s},{i},{f},{b},{t},{g}"


# (lines, column-wise expected to engage)
CASES = {
    "crlf": ([_row(g="1.5\r"), _row(i="8", f="-1.25", g="-2\r")], True),
    "crlf_in_str": ([_row(t="y\r")], True),
    "ragged_short": ([_row(), "a,1"], False),
    "ragged_extra_trailing": ([_row(), _row() + ",extra"], False),
    "empty_int_field": ([_row(i="")], True),
    "empty_float_field": ([_row(f="")], True),
    "empty_str_field": ([_row(s=""), _row(t="")], True),
    "int_plus": ([_row(i="+1")], True),
    "int_space": ([_row(i=" 1")], True),
    "int_underscore": ([_row(i="1_000")], True),
    "int_exponent": ([_row(i="1e5")], True),
    "float_plus_space_underscore": ([_row(f="+1"), _row(f=" 1"),
                                     _row(f="1_000.5")], True),
    "float_exponent": ([_row(f="1e5"), _row(f="-2.5E-3")], True),
    "float_nan_inf": ([_row(f="nan"), _row(f="inf"), _row(f="-Infinity")],
                      True),
    "negative_zero": ([_row(f="-0.00"), _row(f="-0"), _row(i="-0")], True),
    "bare_point_forms": ([_row(f=".5"), _row(f="-.5"), _row(f="5."),
                          _row(f="007.50")], True),
    "lone_point": ([_row(f=".")], True),
    "lone_minus_int": ([_row(i="-")], True),
    "significant_16_17": ([_row(f="1234567890123456"),
                           _row(f="12345678901234567"),
                           _row(f="0.1234567890123456"),
                           _row(f="9007199254740993"),
                           _row(f="900719925474099.3"),
                           _row(f="-90071992547409.93")], True),
    "fraction_23_digits": ([_row(f="0.12345678901234567890123")], True),
    "int_18_19_digits": ([_row(i="123456789012345678"),
                          _row(i="-123456789012345678"),
                          _row(i="-9223372036854775808")], True),
    "int_past_2_63": ([_row(i="9223372036854775808")], True),
    "errors_in_row_order": ([_row(i="9223372036854775808"),
                             _row(i="oops")], True),
    "errors_in_column_order": ([_row(f="oops"),
                                _row(i="9223372036854775808")], True),
    "non_ascii": ([_row(s="é"), _row()], False),
    "line_with_newline": ([_row() + "\n" + _row()], False),
    "empty_chunk": ([], False),
    "bool_forms": ([_row(b="1"), _row(b="no"), _row(b=" Yes"),
                    _row(b="T"), _row(b="")], True),
    "single_row": ([_row()], True),
}


@pytest.mark.parametrize("case", sorted(CASES) + ["tlc"])
def test_columnar_parse_equals_the_per_line_parse(case):
    if case == "tlc":
        lines, schema = _tlc_lines(3000, 2876543210)
        engages = True
    else:
        (lines, engages), schema = CASES[case], MIXED
    specs = _specs(schema)
    width = max(i for i, _, _ in specs) + 1
    assert (csvscan._split_fields(lines, width) is not None) == engages
    want = _outcome(lambda: csvscan.parse_lines(lines, specs))
    got = _outcome(lambda: V.scan_ingest(specs)(lines)[0])
    _assert_same(got, want)
    if engages:  # also each column alone, and in another order
        for spec in specs:
            _assert_same(
                _outcome(lambda: csvscan.parse_columnar(lines, [spec],
                                                        width)),
                _outcome(lambda: csvscan.parse_lines(lines, [spec])))
        _assert_same(
            _outcome(lambda: csvscan.parse_columnar(lines, specs[::-1],
                                                    width)),
            _outcome(lambda: csvscan.parse_lines(lines, specs[::-1])))


def test_columnar_parse_reads_the_tlc_rows_without_python_casts():
    """The generated TLC rows are plain decimals: no field of an int or
    float column takes the Python cast."""
    lines, schema = _tlc_lines(2000, 7)
    specs = _specs(schema)
    text, buf, bounds = csvscan._split_fields(lines, len(schema))
    for idx, dtype, _ in specs:
        if dtype in ("int", "float"):
            vals, slow = csvscan._parse_numbers(buf, *bounds(idx),
                                                dtype == "float")
            assert slow.size == 0


def _decimal(sign, lead, ip, point, frac, frac_len, int_digits):
    body = "0" * lead + (str(ip) if int_digits else "")
    if point:
        body += "." + str(frac).zfill(frac_len)[-frac_len:] if frac_len \
            else "."
    return ("-" if sign else "") + body


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.booleans(), st.integers(0, 3),
                          st.integers(0, 10**19), st.booleans(),
                          st.integers(0, 10**25), st.integers(0, 25),
                          st.booleans()),
                min_size=1, max_size=40))
def test_random_decimal_text_parses_like_python(fields):
    texts = [_decimal(*f) for f in fields]
    lines = [f"x,{t},{t}" for t in texts]
    for dtype in ("int", "float"):
        specs = [(1, dtype, CASTS[dtype]), (2, "str", str)]
        _assert_same(_outcome(lambda: csvscan.parse_columnar(lines, specs,
                                                             3)),
                     _outcome(lambda: csvscan.parse_lines(lines, specs)))
    for t in texts:  # each alone, so one bad field cannot mask another
        line = [f"x,{t},{t}"]
        for dtype in ("int", "float"):
            specs = [(1, dtype, CASTS[dtype])]
            _assert_same(_outcome(lambda: csvscan.parse_columnar(line, specs,
                                                                 3)),
                         _outcome(lambda: csvscan.parse_lines(line, specs)))


def test_fast_float_path_bounds_are_exact():
    """Mantissas just below 2**53 and 17 digits after the point (18
    characters at most) take the fast path, and give float() of the
    text."""
    texts = ["9007199254740991", "0.9007199254740991", "-900719925474.0991",
             ".00000000000000001", "-.00000000000000009", "12345678.9"]
    lines = [f"{t}," for t in texts]
    text, buf, bounds = csvscan._split_fields(lines, 1)
    vals, slow = csvscan._parse_numbers(buf, *bounds(0), True)
    assert slow.size == 0
    want = np.array([float(t) for t in texts])
    assert (vals.view(np.int64) == want.view(np.int64)).all()
    assert math.copysign(1.0, csvscan.parse_columnar(
        ["-0.000"], [(0, "float", float)], 1)[0][0]) == -1.0


TAXI = Schema([("pickup", "str"), ("payment", "int"), ("tip", "float"),
               ("total", "float")])


def _taxi_csv(n, pickup="2015-01-0{d} 0{h}:10:00"):
    return "".join(f"{pickup.format(d=1 + i % 9, h=i % 10)},{1 + i % 2},"
                   f"{i % 7}.25,{i * 1.5}\n" for i in range(n)).encode()


@pytest.mark.parametrize("pickup, counter", [
    ("2015-01-0{d} 0{h}:10:00", "ingest_columnar_chunks"),
    ("2015-01-0{d} 0{h}:10:00é", "ingest_line_chunks")])
def test_chunk_counters_reach_the_scheduler(pickup, counter):
    """A read_csv query's tasks count each chunk's parse, and the
    scheduler sums them into ``device_stats``; the answer is the row
    path's."""
    answers = {}
    for vectorize in (True, False):
        ctx = FlintContext(config=FlintConfig(
            vectorize=vectorize, concurrency=4, vector_batch_rows=64))
        ctx.upload("t.csv", _taxi_csv(500, pickup))
        df = ctx.read_csv("t.csv", TAXI, 4)
        answers[vectorize] = sorted(
            df.where(col("payment") == lit(1))
            .withColumn("hour", col("pickup").substr(12, 2))
            .groupBy("hour")
            .agg(sum_(col("tip")).alias("tips"), count_().alias("n"))
            .collect())
        stats = ctx.last_scheduler.device_stats
        if vectorize:
            other = ({"ingest_columnar_chunks", "ingest_line_chunks"}
                     - {counter}).pop()
            # 500 lines over 4 byte ranges, at most 64 lines a chunk
            assert 8 <= stats[counter] <= 12
            assert stats[other] == 0
        else:
            assert stats["ingest_columnar_chunks"] == 0
            assert stats["ingest_line_chunks"] == 0
    assert answers[True] == answers[False]


def test_columnar_pct_reader():
    spec = importlib.util.spec_from_file_location(
        "columnar_pct", ROOT / "bench" / "metrics" / "ingest.columnar_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({"device_stats": {}}) is None
    assert mod.read({"device_stats": {"kernel_calls": 3}}) is None
    assert mod.read({"device_stats": {"ingest_columnar_chunks": 3,
                                      "ingest_line_chunks": 1}}) == 75.0
    assert mod.read({"device_stats": {"ingest_columnar_chunks": 8,
                                      "ingest_line_chunks": 0}}) == 100.0
