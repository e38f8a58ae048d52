"""Schemas and the expression language for the DataFrame surface.

A ``Schema`` is an ordered list of (name, dtype) fields. The scalar
dtypes are "int", "float", "str", "bool", "date" and "decimal(p,s)",
plus "list:<dtype>" for collect_list outputs. Because the schema is
declared, every lowered shuffle ships schema-declared typed columnar
batches (core.shuffle.batch) instead of sniffing types per batch.

Dates and decimals are exact integers inside the engine: a "date" is
the int count of days since 1970-01-01, and a "decimal(p,s)" is its
unscaled int (``21168.23`` at scale 2 is 2116823). Every operator, both
execution paths and the wire work on those ints; ``collect()`` surfaces
them as ``datetime.date`` and ``decimal.Decimal`` (``surface_fn``).
Decimal arithmetic follows Spark's scale rules: ``+``/``-`` take
max(s1, s2), ``*`` takes s1 + s2, an int counts as scale 0, and ``/``
gives a float; precision is max(p1 - s1, p2 - s2) + s + 1 for ``+``/``-``
and p1 + p2 + 1 for ``*``, capped at 38, and is not enforced on values.
A decimal never meets a float without an explicit ``cast``. A date plus
or minus an ``interval`` of days, months or years is a date (months
clamp to the month's last day); literal dates, intervals and decimals
fold when the expression is built.

Expressions are small trees (``col``, ``lit``, arithmetic / comparison /
boolean operators, ``substr``, ``cast``, ``udf``) that know three things:

  * their output dtype given an input schema (schema propagation),
  * the column names they reference (drives projection pruning), and
  * whether they are DETERMINISTIC (a non-deterministic expression blocks
    predicate pushdown — re-evaluating it below a project or join would
    change results).

``bind(schema)`` compiles an expression to a plain row -> value closure;
the lowering maps those over RDD partitions, and core.serde ships them to
executors like any other task function (closures over lists of compiled
sub-expressions are why serde walks containers).

NULL semantics (SQL three-valued logic): outer joins pad unmatched rows
with None, so every operator here treats None as SQL NULL — arithmetic,
comparisons, substr and cast return None when an operand is None, and
``and``/``or`` follow the three-valued truth tables (False AND x is
False, True OR x is True, anything else involving NULL is NULL). A
Filter drops rows whose predicate evaluates to NULL, same as False. The
vectorized kernels keep row/vector parity by falling back to these row
closures whenever a batch carries None (repro.sql.vectorized). Udf is
the exception: user functions see the raw None and apply their own
semantics.
"""

from __future__ import annotations

import calendar
import datetime
import decimal
import operator
import re
from typing import Callable, Iterable

_NAMED_DTYPES = ("int", "float", "str", "bool", "date")
SCALAR_DTYPES = _NAMED_DTYPES + ("decimal(p,s)",)
_SERDE_CHAR = {"int": "i", "float": "f", "str": "s", "bool": "b",
               "date": "D"}
_DECIMAL = re.compile(r"^decimal\(\s*(\d+)\s*,\s*(\d+)\s*\)$")
#: Spark's widest decimal, and the precision it gives a 64-bit int
MAX_PRECISION = 38
_INT_PRECISION = 20
#: the ordinal of day 0 of the engine's dates
EPOCH_ORDINAL = datetime.date(1970, 1, 1).toordinal()
#: decimal literals fold exactly: no result of these needs more digits
_EXACT = decimal.Context(prec=4 * MAX_PRECISION)


def decimal_type(precision: int, scale: int) -> str:
    return f"decimal({precision},{scale})"


def _decimal_ps(dtype: str) -> tuple | None:
    m = _DECIMAL.match(dtype)
    return (int(m.group(1)), int(m.group(2))) if m else None


def decimal_scale(dtype: str) -> int | None:
    """The scale of a "decimal(p,s)" dtype; None for any other dtype."""
    ps = _decimal_ps(dtype)
    return ps[1] if ps else None


def exact_scale(dtype: str) -> int | None:
    """Digits after the point of an exact number: 0 for "int", s for a
    decimal, None for every other dtype."""
    return 0 if dtype == "int" else decimal_scale(dtype)


def int_backed(dtype: str) -> bool:
    """Held as an int64 column by the vectorized path."""
    return dtype in ("int", "date") or decimal_scale(dtype) is not None


def canonical_dtype(dtype: str) -> str:
    """``dtype`` in its one spelling ("decimal(15,2)"); raises ValueError
    for an unknown dtype."""
    dtype = str(dtype)
    if dtype.startswith("list:") and not dtype.startswith("list:list:"):
        return "list:" + canonical_dtype(dtype[5:])
    ps = _decimal_ps(dtype)
    if ps is not None:
        p, s = ps
        if not 1 <= p <= MAX_PRECISION or s > p:
            raise ValueError(f"bad decimal dtype {dtype!r}: need "
                             f"1 <= p <= {MAX_PRECISION} and s <= p")
        return decimal_type(p, s)
    if dtype not in _NAMED_DTYPES:
        raise ValueError(f"unknown dtype {dtype!r} (have {SCALAR_DTYPES})")
    return dtype


def dtype_serde_char(dtype: str) -> str:
    """Map a DataFrame dtype to the serde column-schema grammar: a
    decimal carries its scale ("d2"), a date its own code ("D")."""
    if dtype.startswith("list:"):
        return "l(%s)" % dtype_serde_char(dtype[5:])
    scale = decimal_scale(dtype)
    if scale is not None:
        return "d%d" % scale
    return _SERDE_CHAR[dtype]


# ------------------------------------------------- dates and decimals


def div_half_up(a: int, b: int) -> int:
    """a / b rounded to the nearest int, halves away from zero."""
    q, r = divmod(abs(a), abs(b))
    if 2 * r >= abs(b):
        q += 1
    return -q if (a < 0) != (b < 0) else q


def unscaled(d: decimal.Decimal, scale: int) -> int:
    """``d`` as an int counted in units of 10**-scale, rounded half up."""
    sign, digits, exp = d.as_tuple()
    if not isinstance(exp, int):
        raise ValueError(f"not a finite decimal: {d}")
    coeff = int("".join(map(str, digits)) or "0")
    shift = exp + scale
    v = coeff * 10**shift if shift >= 0 else div_half_up(coeff,
                                                         10**-shift)
    return -v if sign else v


def parse_decimal(text: str, scale: int) -> int:
    """The unscaled int of decimal text at ``scale``, with no float in
    between ("21168.23" -> 2116823, "17" -> 1700 at scale 2); extra
    digits round half up."""
    try:
        return unscaled(decimal.Decimal(text), scale)
    except decimal.InvalidOperation:
        raise ValueError(f"invalid decimal {text!r}") from None


def parse_date(text: str) -> int:
    """Days since 1970-01-01 of an ISO date ("1998-12-01")."""
    return datetime.date.fromisoformat(text).toordinal() - EPOCH_ORDINAL


def shift_date(days: int, months: int, ndays: int) -> int:
    """``days`` moved by whole months (clamped to the month's last day,
    as Spark's add_months) and then by ``ndays``."""
    if months:
        d = datetime.date.fromordinal(days + EPOCH_ORDINAL)
        y, m = divmod(d.year * 12 + d.month - 1 + months, 12)
        day = min(d.day, calendar.monthrange(y, m + 1)[1])
        days = datetime.date(y, m + 1, day).toordinal() - EPOCH_ORDINAL
    return days + ndays


def _decimal_scan(scale: int):
    def cast(text):
        return parse_decimal(text, scale)
    return cast


def scan_cast(dtype: str) -> Callable:
    """The CSV field parser of ``dtype``: a date to its day number, a
    decimal to its unscaled int."""
    scale = decimal_scale(dtype)
    if scale is not None:
        return _decimal_scan(scale)
    if dtype == "date":
        return parse_date
    return CASTS[dtype]


def _surface_decimal(scale: int):
    def surface(v):
        return None if v is None else decimal.Decimal(f"{v}E-{scale}")
    return surface


def _surface_date(v):
    return (None if v is None
            else datetime.date.fromordinal(v + EPOCH_ORDINAL))


def _surface_value(dtype: str):
    if dtype.startswith("list:"):
        inner = _surface_value(dtype[5:])
        if inner is None:
            return None
        return lambda v: None if v is None else [inner(x) for x in v]
    scale = decimal_scale(dtype)
    if scale is not None:
        return _surface_decimal(scale)
    return _surface_date if dtype == "date" else None


def surface_fn(schema: "Schema") -> Callable | None:
    """Row -> row turning the engine's day numbers and unscaled ints into
    ``datetime.date`` and ``decimal.Decimal``; None where the schema has
    neither."""
    convs = [(i, f) for i, (_, t) in enumerate(schema)
             if (f := _surface_value(t)) is not None]
    if not convs:
        return None

    def surface(row):
        out = list(row)
        for i, f in convs:
            out[i] = f(out[i])
        return tuple(out)
    return surface


def internal_value(dtype: str, v):
    """A user's value of a date or decimal column (``datetime.date``,
    ``decimal.Decimal`` or a whole int) as the engine holds it."""
    if v is None:
        return None
    scale = decimal_scale(dtype)
    if scale is not None and type(v) is int:
        return v * 10**scale
    if scale is not None and type(v) is decimal.Decimal:
        return unscaled(v, scale)
    if dtype == "date" and type(v) is datetime.date:
        return v.toordinal() - EPOCH_ORDINAL
    raise TypeError(f"cannot hold {v!r} in a {dtype} column")


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "t", "yes")


#: CSV field parsers (scan-time) — bools parse from text
CASTS: dict = {"int": int, "float": float, "str": str, "bool": _parse_bool}
#: cast() expression semantics on live values — bools follow Python truth
_RUNTIME_CASTS: dict = {"int": int, "float": float, "str": str,
                        "bool": bool}


class Schema:
    """Ordered, uniquely named, typed columns."""

    __slots__ = ("fields",)

    def __init__(self, fields: Iterable):
        out = []
        seen = set()
        for name, dtype in fields:
            name = str(name)
            if name in seen:
                raise ValueError(f"duplicate column name {name!r} "
                                 f"(alias aggregate/select outputs)")
            seen.add(name)
            try:
                out.append((name, canonical_dtype(dtype)))
            except ValueError as e:
                raise ValueError(f"{e} for column {name!r}") from None
        self.fields = tuple(out)

    @property
    def names(self) -> tuple:
        return tuple(n for n, _ in self.fields)

    def index(self, name: str) -> int:
        for i, (n, _) in enumerate(self.fields):
            if n == name:
                return i
        raise KeyError(f"no column {name!r} in schema "
                       f"[{', '.join(self.names)}]")

    def dtype_of(self, name: str) -> str:
        return self.fields[self.index(name)][1]

    def select(self, names: Iterable[str]) -> "Schema":
        return Schema((n, self.dtype_of(n)) for n in names)

    def serde_tuple(self, names: Iterable[str] | None = None) -> str | None:
        """Declared key/value batch schema ("t(i,s,...)") for a tuple of
        these columns, or None for zero columns (nothing to declare)."""
        names = self.names if names is None else tuple(names)
        if not names:
            return None
        return "t(%s)" % ",".join(
            dtype_serde_char(self.dtype_of(n)) for n in names)

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __eq__(self, other):
        return isinstance(other, Schema) and self.fields == other.fields

    def __hash__(self):
        return hash(self.fields)

    def __repr__(self):
        return "Schema([%s])" % ", ".join(f"{n}:{t}"
                                          for n, t in self.fields)


# ------------------------------------------------------------ expressions


class Expr:
    def children(self) -> list:
        return []

    def refs(self) -> set:
        out: set = set()
        for c in self.children():
            out |= c.refs()
        return out

    @property
    def deterministic(self) -> bool:
        return all(c.deterministic for c in self.children())

    def dtype(self, schema: Schema) -> str:
        raise NotImplementedError

    def bind(self, schema: Schema) -> Callable:
        raise NotImplementedError

    def bind_vec(self, schema: Schema) -> Callable:
        """Vectorized sibling of ``bind()``: compile to a whole-batch
        closure ``fn(cols, n) -> column`` evaluating numpy arrays /
        Python lists over a column batch (repro.sql.vectorized,
        docs/vectorized_execution.md). Raises
        ``vectorized.VectorizeUnsupported`` for expressions with no
        array form (udf, non-scalar operands) — the lowering then keeps
        the per-row closures for that operator."""
        from repro.sql.vectorized import compile_expr
        return compile_expr(self, schema)

    def substitute(self, mapping: dict) -> "Expr":
        """Replace column references per ``mapping`` (name -> Expr) —
        predicate pushdown through a Project rewrites in terms of the
        project's inputs."""
        raise NotImplementedError

    def sql(self) -> str:
        raise NotImplementedError

    # ------------------------------------------------- operator building
    def _bin(self, op: str, other) -> "Expr":
        return fold(BinOp(op, self, _as_expr(other)))

    def __add__(self, other):
        return self._bin("+", other)

    def __sub__(self, other):
        return self._bin("-", other)

    def __mul__(self, other):
        return self._bin("*", other)

    def __radd__(self, other):
        return fold(BinOp("+", _as_expr(other), self))

    def __rsub__(self, other):
        return fold(BinOp("-", _as_expr(other), self))

    def __rmul__(self, other):
        return fold(BinOp("*", _as_expr(other), self))

    def __truediv__(self, other):
        return self._bin("/", other)

    def __mod__(self, other):
        return self._bin("%", other)

    def __eq__(self, other):  # noqa: builds an expression, not a bool
        return self._bin("=", other)

    def __ne__(self, other):
        return self._bin("!=", other)

    def __lt__(self, other):
        return self._bin("<", other)

    def __le__(self, other):
        return self._bin("<=", other)

    def __gt__(self, other):
        return self._bin(">", other)

    def __ge__(self, other):
        return self._bin(">=", other)

    def __and__(self, other):
        return self._bin("and", other)

    def __or__(self, other):
        return self._bin("or", other)

    def __invert__(self):
        return Not(self)

    __hash__ = object.__hash__  # __eq__ builds exprs; identity hash is fine

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def substr(self, start: int, length: int) -> "Substr":
        """1-based substring, SQL-style."""
        return Substr(self, start, length)

    def cast(self, dtype: str) -> "Cast":
        return Cast(self, dtype)

    def between(self, lo, hi) -> "Expr":
        """SQL BETWEEN: ``lo <= self <= hi``, both ends inclusive."""
        return (self >= lo) & (self <= hi)

    def __repr__(self):
        return f"<expr {self.sql()}>"


def _as_expr(v) -> Expr:
    return v if isinstance(v, Expr) else Lit(v)


class Col(Expr):
    def __init__(self, name: str):
        self.name = name

    def refs(self):
        return {self.name}

    def dtype(self, schema):
        return schema.dtype_of(self.name)

    def bind(self, schema):
        return operator.itemgetter(schema.index(self.name))

    def substitute(self, mapping):
        return mapping.get(self.name, self)

    def sql(self):
        return self.name


_LIT_DTYPE = {bool: "bool", int: "int", float: "float", str: "str"}


class Lit(Expr):
    """A constant. ``value`` is as the user gave it; ``internal`` is how
    the engine holds it (a date's day number, a decimal's unscaled
    int at the literal's own scale)."""

    def __init__(self, value):
        t = type(value)
        if t is decimal.Decimal:
            if not value.is_finite():
                raise TypeError(f"unsupported literal {value!r}")
            scale = max(0, -value.as_tuple().exponent)
            self.internal = unscaled(value, scale)
            digits = len(str(abs(self.internal)))
            if max(digits, scale) > MAX_PRECISION:
                raise TypeError(f"decimal literal {value} has more than "
                                f"{MAX_PRECISION} digits")
            self._dtype = decimal_type(max(digits, scale, 1), scale)
        elif t is datetime.date:
            self.internal = value.toordinal() - EPOCH_ORDINAL
            self._dtype = "date"
        elif t in _LIT_DTYPE:
            self.internal = value
            self._dtype = _LIT_DTYPE[t]
        else:
            raise TypeError(f"unsupported literal {value!r} "
                            f"(int/float/str/bool/date/Decimal)")
        self.value = value

    def dtype(self, schema):
        return self._dtype

    def bind(self, schema):
        v = self.internal
        return lambda row: v

    def substitute(self, mapping):
        return self

    def sql(self):
        if self._dtype == "date":
            return f"date '{self.value.isoformat()}'"
        if self._dtype.startswith("decimal"):
            return str(self.value)
        return repr(self.value)


INTERVAL_UNITS = ("day", "month", "year")


class Interval(Expr):
    """``interval 'n' unit``: added to or subtracted from a date only."""

    def __init__(self, n: int, unit: str):
        if type(n) is not int or unit not in INTERVAL_UNITS:
            raise ValueError(f"interval needs an int count and a unit of "
                             f"{INTERVAL_UNITS}, got {n!r} {unit!r}")
        self.n = n
        self.unit = unit
        self.months = n * 12 if unit == "year" else (
            n if unit == "month" else 0)
        self.days = n if unit == "day" else 0

    def dtype(self, schema):
        return "interval"

    def bind(self, schema):
        raise TypeError(f"{self.sql()}: an interval only shifts a date")

    def substitute(self, mapping):
        return self

    def sql(self):
        return f"interval '{self.n}' {self.unit}"


def _div(a, b):
    return a / b


_OPS: dict = {
    "+": operator.add, "-": operator.sub, "*": operator.mul,
    "/": _div, "%": operator.mod,
    "=": operator.eq, "!=": operator.ne, "<": operator.lt,
    "<=": operator.le, ">": operator.gt, ">=": operator.ge,
    "and": operator.and_, "or": operator.or_,
}
_ARITH = ("+", "-", "*", "%")
_NUMERIC = ("int", "float")


class BinOp(Expr):
    def __init__(self, op: str, left: Expr, right: Expr):
        if op not in _OPS:
            raise ValueError(f"unknown operator {op!r}")
        self.op = op
        self.left = left
        self.right = right

    def children(self):
        return [self.left, self.right]

    def dtype(self, schema):
        lt, rt = self.left.dtype(schema), self.right.dtype(schema)
        if "interval" in (lt, rt):
            if self.interval_sign(lt, rt) is None:
                raise TypeError(f"{self.sql()}: an interval is only added "
                                f"to or subtracted from a date")
            return "date"
        if self.op in _ARITH and lt == rt == "date":
            if self.op != "-":
                raise TypeError(f"{self.sql()}: dates only subtract")
            return "int"  # days between
        if (decimal_scale(lt) is not None or decimal_scale(rt) is not None):
            return _decimal_result(self, lt, rt)
        if self.op in _ARITH:
            if lt not in _NUMERIC or rt not in _NUMERIC:
                if self.op == "+" and lt == rt == "str":
                    return "str"  # concatenation
                raise TypeError(f"{self.sql()}: arithmetic needs numeric "
                                f"operands, got {lt}/{rt}")
            return "float" if "float" in (lt, rt) else "int"
        if self.op == "/":
            if lt not in _NUMERIC or rt not in _NUMERIC:
                raise TypeError(f"{self.sql()}: division needs numeric "
                                f"operands, got {lt}/{rt}")
            return "float"
        if self.op in ("and", "or"):
            if not (lt == rt == "bool"):
                raise TypeError(f"{self.sql()}: boolean operands "
                                f"required, got {lt}/{rt}")
            return "bool"
        # comparisons: mismatched operand dtypes fail at PLAN time like
        # every other type error, not mid-execution on a billed task
        if lt != rt and not (lt in _NUMERIC and rt in _NUMERIC):
            raise TypeError(f"{self.sql()}: cannot compare {lt} with "
                            f"{rt}")
        return "bool"

    def interval_sign(self, lt: str, rt: str) -> int | None:
        """+1 / -1 for date + / - interval (or interval + date), else
        None."""
        if lt == "date" and rt == "interval" and self.op in ("+", "-"):
            return 1 if self.op == "+" else -1
        if lt == "interval" and rt == "date" and self.op == "+":
            return 1
        return None

    def scales(self, schema) -> tuple:
        """(left multiplier, right multiplier) that bring two exact
        numbers to one scale for ``+``/``-`` and comparisons; (1, 1)
        where nothing is aligned."""
        ls = exact_scale(self.left.dtype(schema))
        rs = exact_scale(self.right.dtype(schema))
        if ls is None or rs is None or self.op in ("*", "/", "%",
                                                   "and", "or"):
            return 1, 1
        s = max(ls, rs)
        return 10**(s - ls), 10**(s - rs)

    def bind(self, schema):
        lt, rt = self.left.dtype(schema), self.right.dtype(schema)
        if "interval" in (lt, rt):
            return self._bind_interval(schema, lt, rt)
        lf, rf = self.left.bind(schema), self.right.bind(schema)
        if self.op == "and":
            # SHORT-CIRCUIT, not operator.and_: the optimizer merges
            # sequential filters into one conjunction, and the later
            # guard must never evaluate on rows the earlier one excludes
            # (e.g. `n != 0` guarding `100 / n`). Three-valued: a False
            # side wins without looking at the other; NULL otherwise
            # taints the result unless the other side is False.
            def and_(row):
                a = lf(row)
                if a is not None and not a:
                    return False
                b = rf(row)
                if b is not None and not b:
                    return False
                return None if a is None or b is None else True
            return and_
        if self.op == "or":
            def or_(row):
                a = lf(row)
                if a is not None and a:
                    return True
                b = rf(row)
                if b is not None and b:
                    return True
                return None if a is None or b is None else False
            return or_
        fn = _OPS[self.op]
        ma, mb = self.scales(schema)
        if self.op == "/" and not (decimal_scale(lt) is None
                                   and decimal_scale(rt) is None):
            # exact quotient of the two scaled ints, correctly rounded
            fn = _decimal_div(10**(exact_scale(rt)), 10**(exact_scale(lt)))
        elif ma != 1 or mb != 1:
            fn = _aligned(fn, ma, mb)

        def apply(row):
            a = lf(row)
            if a is None:
                return None
            b = rf(row)
            return None if b is None else fn(a, b)
        return apply

    def _bind_interval(self, schema, lt, rt):
        sign = self.interval_sign(lt, rt)
        date_side, iv = ((self.left, self.right) if lt == "date"
                         else (self.right, self.left))
        if not isinstance(iv, Interval):
            raise TypeError(f"{self.sql()}: the interval must be literal")
        f = date_side.bind(schema)
        months, days = sign * iv.months, sign * iv.days

        def shift(row):
            v = f(row)
            return None if v is None else shift_date(v, months, days)
        return shift

    def substitute(self, mapping):
        return BinOp(self.op, self.left.substitute(mapping),
                     self.right.substitute(mapping))

    def sql(self):
        return f"({self.left.sql()} {self.op} {self.right.sql()})"


def _aligned(fn, ma: int, mb: int):
    def aligned(a, b):
        return fn(a * ma, b * mb)
    return aligned


def _decimal_div(ma: int, mb: int):
    def div(a, b):
        return (a * ma) / (b * mb)
    return div


def _decimal_result(node: BinOp, lt: str, rt: str) -> str:
    """The dtype of a BinOp with a decimal operand."""
    ls, rs = exact_scale(lt), exact_scale(rt)
    if ls is None or rs is None:
        raise TypeError(f"{node.sql()}: a decimal meets only ints and "
                        f"decimals, got {lt}/{rt} (cast the other side)")
    op = node.op
    if op in ("and", "or") or op == "%":
        raise TypeError(f"{node.sql()}: {op} does not take decimals")
    if op == "/":
        return "float"
    if op not in _ARITH:
        return "bool"
    lp = _INT_PRECISION if lt == "int" else _decimal_ps(lt)[0]
    rp = _INT_PRECISION if rt == "int" else _decimal_ps(rt)[0]
    if op == "*":
        scale, precision = ls + rs, lp + rp + 1
    else:
        scale = max(ls, rs)
        precision = max(lp - ls, rp - rs) + scale + 1
    if scale > MAX_PRECISION:
        raise TypeError(f"{node.sql()}: scale {scale} is past "
                        f"{MAX_PRECISION}")
    return decimal_type(min(precision, MAX_PRECISION), scale)


def fold(e: Expr) -> Expr:
    """Fold a BinOp of literal dates, intervals and decimals into one
    literal (``date '1998-12-01' - interval '90' day`` is ``date
    '1998-09-02'``); any other expression comes back as it is."""
    if not isinstance(e, BinOp) or e.op not in ("+", "-", "*"):
        return e
    a, b = e.left, e.right
    if {type(a), type(b)} == {Lit, Interval}:
        sign = e.interval_sign(a.dtype(None), b.dtype(None))
        if sign is None:
            return e
        day, iv = (a, b) if isinstance(b, Interval) else (b, a)
        days = shift_date(day.internal, sign * iv.months, sign * iv.days)
        return Lit(datetime.date.fromordinal(days + EPOCH_ORDINAL))
    if not (isinstance(a, Lit) and isinstance(b, Lit)):
        return e
    types_ = {type(a.value), type(b.value)}
    if decimal.Decimal not in types_ or not types_ <= {decimal.Decimal, int}:
        return e
    fn = {"+": _EXACT.add, "-": _EXACT.subtract, "*": _EXACT.multiply}
    return Lit(fn[e.op](decimal.Decimal(a.value), decimal.Decimal(b.value)))


class Not(Expr):
    def __init__(self, child: Expr):
        self.child = child

    def children(self):
        return [self.child]

    def dtype(self, schema):
        if self.child.dtype(schema) != "bool":
            raise TypeError(f"{self.sql()}: boolean operand required")
        return "bool"

    def bind(self, schema):
        f = self.child.bind(schema)

        def not_(row):
            v = f(row)
            return None if v is None else not v
        return not_

    def substitute(self, mapping):
        return Not(self.child.substitute(mapping))

    def sql(self):
        return f"(not {self.child.sql()})"


class Substr(Expr):
    """1-based fixed-length substring (SQL SUBSTR)."""

    def __init__(self, child: Expr, start: int, length: int):
        if start < 1 or length < 0:
            # a 0-based habit would silently slice s[-1:...] to ""
            raise ValueError(f"substr is 1-based: start >= 1 and "
                             f"length >= 0 (got {start}, {length})")
        self.child = child
        self.start = start
        self.length = length

    def children(self):
        return [self.child]

    def dtype(self, schema):
        if self.child.dtype(schema) != "str":
            raise TypeError(f"{self.sql()}: substr needs a str operand")
        return "str"

    def bind(self, schema):
        f = self.child.bind(schema)
        lo = self.start - 1
        hi = lo + self.length

        def substr(row):
            s = f(row)
            return None if s is None else s[lo:hi]
        return substr

    def substitute(self, mapping):
        return Substr(self.child.substitute(mapping), self.start,
                      self.length)

    def sql(self):
        return f"substr({self.child.sql()}, {self.start}, {self.length})"


def cast_fn(src: str, to: str) -> Callable:
    """cast() on one live value of dtype ``src`` (as the engine holds
    it) to dtype ``to``; raises TypeError for a pair with no cast. Into
    a decimal, extra digits round half up; a decimal to an int
    truncates toward zero, as Spark."""
    if src == to:
        return _identity
    to_scale, src_scale = decimal_scale(to), decimal_scale(src)
    if to_scale is not None:
        if src == "int":
            return _times(10**to_scale)
        if src_scale is not None:
            if to_scale >= src_scale:
                return _times(10**(to_scale - src_scale))
            return _rescale_down(10**(src_scale - to_scale))
        if src == "str":
            return _decimal_scan(to_scale)
        if src == "float":
            return _float_to_decimal(to_scale)
    elif to == "date":
        if src == "str":
            return parse_date
    elif src_scale is not None:
        if to == "float":
            return _over(10**src_scale)
        if to == "int":
            return _truncate(10**src_scale)
        if to == "str":
            return _decimal_text(src_scale)
        if to == "bool":
            return bool
    elif src == "date":
        if to == "str":
            return _date_text
    elif not src.startswith("list:"):
        return _RUNTIME_CASTS[to]
    raise TypeError(f"no cast from {src} to {to}")


def _identity(v):
    return v


def _times(m: int):
    def times(v):
        return v * m
    return times


def _over(m: int):
    def over(v):
        return v / m  # the exact quotient, correctly rounded
    return over


def _rescale_down(m: int):
    def rescale(v):
        return div_half_up(v, m)
    return rescale


def _truncate(m: int):
    def truncate(v):
        return -(-v // m) if v < 0 else v // m
    return truncate


def _float_to_decimal(scale: int):
    def cast(v):
        return unscaled(decimal.Decimal(v), scale)
    return cast


def _decimal_text(scale: int):
    surface = _surface_decimal(scale)

    def text(v):
        return str(surface(v))
    return text


def _date_text(v):
    return _surface_date(v).isoformat()


class Cast(Expr):
    def __init__(self, child: Expr, to: str):
        try:
            to = canonical_dtype(to)
        except ValueError:
            raise ValueError(f"cannot cast to {to!r}") from None
        if to.startswith("list:"):
            raise ValueError(f"cannot cast to {to!r}")
        self.child = child
        self.to = to

    def children(self):
        return [self.child]

    def dtype(self, schema):
        cast_fn(self.child.dtype(schema), self.to)  # validate the pair
        return self.to

    def bind(self, schema):
        f = self.child.bind(schema)
        caster = cast_fn(self.child.dtype(schema), self.to)

        def cast(row):
            v = f(row)
            return None if v is None else caster(v)
        return cast

    def substitute(self, mapping):
        return Cast(self.child.substitute(mapping), self.to)

    def sql(self):
        return f"cast({self.child.sql()} as {self.to})"


class Udf(Expr):
    """A user function lifted to an expression. ``deterministic=False``
    marks it as a pushdown barrier (see optimizer)."""

    def __init__(self, fn: Callable, dtype: str, args: list,
                 name: str | None = None, deterministic: bool = True):
        self.fn = fn
        self._dtype = dtype
        self.args = [_as_expr(a) for a in args]
        self.name = name or getattr(fn, "__name__", "udf")
        self._deterministic = deterministic

    def children(self):
        return list(self.args)

    @property
    def deterministic(self):
        return self._deterministic and super().deterministic

    def dtype(self, schema):
        for a in self.args:
            a.dtype(schema)
        return self._dtype

    def bind(self, schema):
        fn = self.fn
        bound = [a.bind(schema) for a in self.args]
        return lambda row: fn(*[b(row) for b in bound])

    def substitute(self, mapping):
        return Udf(self.fn, self._dtype,
                   [a.substitute(mapping) for a in self.args],
                   name=self.name, deterministic=self._deterministic)

    def sql(self):
        tag = "" if self._deterministic else "!"
        return f"{self.name}{tag}({', '.join(a.sql() for a in self.args)})"


class Alias(Expr):
    """Names an expression for select/agg output; transparent otherwise."""

    def __init__(self, child: Expr, name: str):
        self.child = child
        self.name = name

    def children(self):
        return [self.child]

    def dtype(self, schema):
        return self.child.dtype(schema)

    def bind(self, schema):
        return self.child.bind(schema)

    def substitute(self, mapping):
        return Alias(self.child.substitute(mapping), self.name)

    def sql(self):
        return self.child.sql()


# -------------------------------------------------------------- aggregates

AGG_OPS = ("sum", "count", "min", "max", "avg", "collect_list")


class AggExpr:
    """An aggregate over a group. All ops except collect_list are
    ALGEBRAIC — they decompose into per-partition partials merged by an
    associative combiner, which is what lets the optimizer select the
    map-side-combine (reduceByKey) lowering."""

    def __init__(self, op: str, child: Expr | None = None,
                 name: str | None = None):
        if op not in AGG_OPS:
            raise ValueError(f"unknown aggregate {op!r}")
        if child is None and op != "count":
            raise ValueError(f"{op} needs an argument expression")
        self.op = op
        self.child = child
        self.name = name or self.sql()

    @property
    def algebraic(self) -> bool:
        return self.op != "collect_list"

    def refs(self) -> set:
        return self.child.refs() if self.child is not None else set()

    def dtype(self, schema: Schema) -> str:
        ct = self.child.dtype(schema) if self.child is not None else None
        if self.op == "count":
            return "int"
        ps = _decimal_ps(ct) if ct is not None else None
        if self.op in ("sum", "avg") and ct not in _NUMERIC and ps is None:
            raise TypeError(f"{self.sql()}: {self.op} needs a numeric arg")
        if self.op == "avg":
            if ps is None:
                return "float"
            # Spark: decimal(p + 4, s + 4), rounded half up
            return decimal_type(min(ps[0] + 4, MAX_PRECISION),
                                min(ps[1] + 4, MAX_PRECISION))
        if self.op == "sum" and ps is not None:
            return decimal_type(min(ps[0] + 10, MAX_PRECISION), ps[1])
        if self.op == "collect_list":
            return f"list:{ct}"
        return ct  # sum/min/max keep the argument dtype

    def alias(self, name: str) -> "AggExpr":
        return AggExpr(self.op, self.child, name=name)

    def empty_value(self):
        """The aggregate over no rows (a global aggregate of an empty
        input): 0 for count, [] for collect_list, NULL otherwise."""
        return {"count": 0, "collect_list": []}.get(self.op)

    def avg_fn(self, schema: Schema) -> Callable:
        """(sum, count) -> avg: a float, or for a decimal the unscaled
        quotient at the result's scale, rounded half up."""
        scale = decimal_scale(self.child.dtype(schema))
        if scale is None:
            return operator.truediv
        m = 10**(decimal_scale(self.dtype(schema)) - scale)

        def avg(total, count):
            return div_half_up(total * m, count)
        return avg

    def substitute(self, mapping) -> "AggExpr":
        child = (self.child.substitute(mapping)
                 if self.child is not None else None)
        return AggExpr(self.op, child, name=self.name)

    def sql(self) -> str:
        arg = self.child.sql() if self.child is not None else "*"
        return f"{self.op}({arg})"

    def __repr__(self):
        return f"<agg {self.name}:={self.sql()}>"


# ------------------------------------------------------------- public API


def col(name: str) -> Col:
    return Col(name)


def lit(value) -> Lit:
    return Lit(value)


def interval(n: int, unit: str) -> Interval:
    """``interval 'n' day|month|year``, to add to or subtract from a
    date: ``lit(date(1998, 12, 1)) - interval(90, "day")``."""
    return Interval(n, unit)


def udf(fn: Callable, dtype: str, *, name: str | None = None,
        deterministic: bool = True) -> Callable:
    """Lift ``fn`` into the expression language:
    ``hour = udf(int, "int"); hour(col("h"))``."""
    def build(*args) -> Udf:
        return Udf(fn, dtype, list(args), name=name,
                   deterministic=deterministic)
    return build


def sum_(e) -> AggExpr:
    return AggExpr("sum", _as_expr(e))


def count_(e=None) -> AggExpr:
    return AggExpr("count", _as_expr(e) if e is not None else None)


def min_(e) -> AggExpr:
    return AggExpr("min", _as_expr(e))


def max_(e) -> AggExpr:
    return AggExpr("max", _as_expr(e))


def avg_(e) -> AggExpr:
    return AggExpr("avg", _as_expr(e))


def collect_list(e) -> AggExpr:
    return AggExpr("collect_list", _as_expr(e))


def split_conjuncts(pred: Expr) -> list:
    """Flatten an AND tree into its conjuncts (predicate pushdown splits
    a filter and pushes each conjunct as far down as it can go)."""
    if isinstance(pred, BinOp) and pred.op == "and":
        return split_conjuncts(pred.left) + split_conjuncts(pred.right)
    if isinstance(pred, Alias):
        return split_conjuncts(pred.child)
    return [pred]


def join_conjuncts(preds: list) -> Expr:
    out = preds[0]
    for p in preds[1:]:
        out = BinOp("and", out, p)
    return out
