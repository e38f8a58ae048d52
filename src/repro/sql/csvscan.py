"""CSV lines to typed columns: the scan's parse for the fused vectorized
operator (``sql/vectorized.py:scan_ingest``, docs/vectorized_execution.md).

``specs`` is [(field_idx, dtype, cast)] per pruned output column, ``cast``
the exact Python cast of the dtype (``int``, ``float``, ``str``, the bool
parse, or ``repro.sql.expr.scan_cast`` of a date or decimal). Columns
come back as the vectorized path holds them: int / float / bool as numpy
int64 / float64 / bool arrays, a date or decimal as an int64 array of
day numbers or unscaled ints, str as a list.

* ``parse_lines`` splits every line and casts each pruned field with its
  Python cast: the per-line parse, for any chunk.
* ``parse_columnar`` joins an ASCII chunk whose lines all hold the same
  number of fields into one byte buffer, finds every separator at once,
  and reads int, float and decimal fields, and ``YYYY-MM-DD`` dates, a
  whole column at a time; a decimal goes straight from its digits to its
  unscaled int, with no float in between. It gives the
  per-line parse's values bit for bit: a field outside the strict decimal
  forms goes through its Python cast, on the same text, in row order, so
  it raises what the per-line parse raises. Other chunks return None.

The executors rebuild task code from its source functions and the
globals they name; code that reaches this module through the module
object ships as one name, its tables and functions staying here.
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.sql.expr import decimal_scale

_NP_DTYPE = {"int": np.int64, "float": np.float64, "bool": np.bool_,
             "date": np.int64}
_COMMA, _NEWLINE, _MINUS, _DASH = b",\n--"

#: the longest field, minus its sign, that the column-wise number parse
#: reads: 18 characters, so a field's digits always fit an int64
_MAX_DIGITS = 18
_POW10 = 10 ** np.arange(_MAX_DIGITS + 1, dtype=np.int64)
#: Clinger's fast path: a mantissa below 2**53 and 10**k for k <= 22 are
#: both exact doubles, so the one correctly rounded division m / 10**k
#: equals float() of the decimal text bit for bit
_EXACT_MANTISSA = 2**53
_POW10_F64 = np.array([float(10**k) for k in range(_MAX_DIGITS + 1)])
#: per byte: its digit value (0 for every other byte), and its class in a
#: field: 0 for a digit or a separator (which pads a field on its left),
#: 1 for a decimal point, _BAD for anything else. Float tables, so that
#: the row sums below are BLAS products: every partial sum is an integer
#: below 2**53, exact in any order
_DIGIT = np.zeros(256)
_DIGIT[48:58] = np.arange(10)
_BAD = 32
_CLASS = np.full(256, float(_BAD))
_CLASS[48:58] = 0
_CLASS[[_COMMA, _NEWLINE]] = 0
_CLASS[ord(".")] = 1
#: by place (0 the last byte of a field): weights giving a row's class
#: sum and its class-weighted place; and weights giving its digits as two
#: integers below 10**10, the places below _HALF and those above
_HALF = 9
_PLACES = np.arange(_MAX_DIGITS + 1)
_CLASS_WEIGHTS = np.stack([np.ones(_PLACES.size), _PLACES], axis=1)
_DIGIT_WEIGHTS = np.stack(
    [np.where(_PLACES < _HALF, 10.0 ** _PLACES, 0),
     np.where(_PLACES >= _HALF, 10.0 ** (_PLACES - _HALF), 0)], axis=1)


def _np_dtype(dtype: str):
    return np.int64 if decimal_scale(dtype) is not None else _NP_DTYPE[dtype]


def parse_lines(lines, specs) -> list:
    """Split every line, then cast each pruned field with its Python
    cast, collected straight into arrays."""
    parts = [ln.split(",") for ln in lines]
    n = len(parts)
    cols = []
    for idx, dtype, cast in specs:
        raw = [p[idx] for p in parts]
        if dtype == "str":
            cols.append([cast(r) for r in raw])
        else:
            cols.append(np.fromiter(map(cast, raw),
                                    dtype=_np_dtype(dtype), count=n))
    return cols


def parse_columnar(lines, specs, width: int) -> list | None:
    """The columns of a chunk parsed from one byte buffer, or None where
    the chunk must take ``parse_lines``: empty, not ASCII, lines of
    unequal field counts, or fewer than ``width`` fields."""
    fields = _split_fields(lines, width)
    if fields is None:
        return None
    text, buf, bounds = fields
    cols = []
    for idx, dtype, cast in specs:
        start, end = bounds(idx)
        if dtype in ("int", "float"):
            col, slow = _parse_numbers(buf, start, end, dtype == "float")
        elif (scale := decimal_scale(dtype)) is not None:
            col, slow = _parse_numbers(buf, start, end, True, scale)
        elif dtype == "date":
            col, slow = _parse_dates(buf, start, end)
        elif dtype == "str":
            cols.append(_substrings(text, start, end))
            continue
        else:
            cols.append(np.fromiter(map(cast, _substrings(text, start, end)),
                                    dtype=_NP_DTYPE[dtype], count=len(start)))
            continue
        if slow.size:  # Python casts, in row order as per line
            col[slow] = np.fromiter(
                map(cast, _substrings(text, start[slow], end[slow])),
                dtype=col.dtype, count=slow.size)
        cols.append(col)
    return cols


def _split_fields(lines, width):
    """(text, buf, bounds) of a chunk joined into one buffer, each line
    ending in a newline, where ``bounds(idx)`` gives the (start, end)
    offsets of field ``idx`` in every line; None as for
    ``parse_columnar``."""
    n = len(lines)
    if not n:
        return None
    text = "\n".join(itertools.chain(lines, ("",)))
    if not text.isascii():
        return None
    buf = np.frombuffer(text.encode("ascii"), dtype=np.uint8)
    is_sep = buf == _COMMA
    is_sep |= buf == _NEWLINE
    seps = np.flatnonzero(is_sep)
    n_fields = seps.size // n
    if seps.size != n * n_fields or n_fields < width:
        return None
    # n_fields separators to a line, the last a newline, and no other
    # newline: then each line has n_fields fields
    is_newline = buf[seps] == _NEWLINE
    if (np.count_nonzero(is_newline) != n
            or not is_newline[n_fields - 1::n_fields].all()):
        return None
    line_starts = np.empty(n, dtype=seps.dtype)
    line_starts[0] = 0
    line_starts[1:] = seps[n_fields - 1:-1:n_fields] + 1

    def bounds(idx):
        start = line_starts if idx == 0 else seps[idx - 1::n_fields] + 1
        return start, seps[idx::n_fields]
    return text, buf, bounds


def _substrings(text, start, end) -> list:
    return [text[a:b] for a, b in zip(start.tolist(), end.tolist())]


def _parse_numbers(buf, start, end, is_float, scale=None):
    """Parse the fields ``buf[start:end]`` of one column at once. The
    strict forms are ``-?[0-9]+`` for ints and ``-?[0-9]*\\.?[0-9]*`` with
    a digit for floats and decimals, at most ``_MAX_DIGITS`` characters
    after the sign; a float's mantissa must also lie below 2**53, and a
    decimal may have at most ``scale`` digits after its point and at most
    ``_MAX_DIGITS`` digits once scaled; ``scale`` is given for a decimal
    only. Returns (values, slow): the rows in ``slow`` break a bound and
    hold no value yet."""
    lens = end - start
    w = int(min(lens.max(), _MAX_DIGITS + 1))
    # the last w bytes of each field, right-aligned: the bytes left of a
    # short field's start read its separator, a 0 of class 0
    pos = end[:, None] - np.arange(w, 0, -1)
    np.maximum(pos, (start - 1)[:, None], out=pos)
    field = buf.take(pos)
    odd, point_place = (_CLASS.take(field) @ _CLASS_WEIGHTS[:w][::-1]).T
    lo, hi = (_DIGIT.take(field) @ _DIGIT_WEIGHTS[:w][::-1]).T
    # the row's digits as one integer, a point read as a 0 digit
    m = hi.astype(np.int64) * 10**_HALF + lo.astype(np.int64)
    # a leading minus sign counts as one _BAD, at place lens - 1
    signed = buf[start] == _MINUS
    odd = odd.astype(np.int64) - _BAD * signed
    body = lens - signed  # characters after the sign
    ok = (odd <= is_float) & (body > odd) & (body <= _MAX_DIGITS)
    if is_float:
        # k digits after the point, the place of its 0 digit in m
        point = ok & (odd == 1)
        k = np.where(point, point_place.astype(np.int64)
                     - _BAD * signed * (lens - 1), 0)
        # drop that 0: with L the digits left of it, m - 9 L 10**k
        left = np.where(point, m // _POW10[k + 1], 0)
        m -= 9 * left * _POW10[k]
        if scale is None:
            ok &= m < _EXACT_MANTISSA
            vals = m / _POW10_F64[k]
        else:  # decimal: the digits times 10**(scale - k), exact
            digits = body - point
            ok &= (k <= scale) & (digits + scale - k <= _MAX_DIGITS)
            vals = m * _POW10[np.where(ok, scale - k, 0)]
    else:
        vals = m
    np.negative(vals, out=vals, where=signed)
    return vals, np.flatnonzero(~ok)


#: days before each month of a common year, and the days in it
_MONTH_START = np.array([0, 31, 59, 90, 120, 151, 181, 212, 243, 273, 304,
                         334])
_MONTH_DAYS = np.array([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])
_DATE_DIGITS = np.array([0, 1, 2, 3, 5, 6, 8, 9])
#: days from 0001-01-01 to 1970-01-01
_EPOCH = 719162


def _parse_dates(buf, start, end):
    """Day numbers (days since 1970-01-01) of the ``YYYY-MM-DD`` fields
    of one column at once; (values, slow) as ``_parse_numbers``, a field
    of any other form or no such day being slow."""
    n = start.size
    vals = np.zeros(n, dtype=np.int64)
    ok = (end - start) == 10
    rows = np.flatnonzero(ok)
    field = buf.take(start[rows, None] + np.arange(10))
    d = field[:, _DATE_DIGITS].astype(np.int64) - 48
    good = ((d >= 0) & (d <= 9)).all(axis=1)
    good &= (field[:, 4] == _DASH) & (field[:, 7] == _DASH)
    y = d[:, 0] * 1000 + d[:, 1] * 100 + d[:, 2] * 10 + d[:, 3]
    m = d[:, 4] * 10 + d[:, 5]
    day = d[:, 6] * 10 + d[:, 7]
    good &= (y >= 1) & (m >= 1) & (m <= 12)
    mi = np.clip(m - 1, 0, 11)
    leap = (y % 4 == 0) & ((y % 100 != 0) | (y % 400 == 0))
    good &= (day >= 1) & (day <= _MONTH_DAYS[mi] + (leap & (mi == 1)))
    py = y - 1
    vals[rows] = (py * 365 + py // 4 - py // 100 + py // 400
                  + _MONTH_START[mi] + (leap & (mi >= 2)) + day - 1
                  - _EPOCH)
    ok[rows] = good
    return vals, np.flatnonzero(~ok)
