"""Vectorized columnar execution: compile the expression language to
array kernels over whole column batches (docs/vectorized_execution.md).

``compile_expr(expr, schema)`` is the vectorized sibling of
``Expr.bind()``: instead of a row -> value closure it produces a
``fn(cols, n) -> column`` closure evaluating a whole batch at once. A
column is one of three shapes, fixed by dtype:

  * "int" / "float" / "bool"  -> a numpy int64 / float64 / bool array
  * "date" / "decimal(p,s)"   -> a numpy int64 array of day numbers /
                                 unscaled ints (repro.sql.expr)
  * "str" and "list:..."      -> a plain Python list
  * a literal                 -> a bare Python scalar (broadcasts), a
                                 date or decimal as the engine holds it

The contract with the row path is BIT-IDENTICAL RESULTS. Wherever a
numpy shortcut could diverge from the Python semantics of the bound row
closures, the compiled code either takes an exact path or raises
``VectorFallback`` so the fused operator re-runs the chunk through the
original row closures:

  * int64 arithmetic wraps silently in numpy (and ``np.errstate`` does
    NOT trap it) — every int, date and decimal +/-/*, and every scale
    alignment of a decimal, is shadowed in float64 and any magnitude
    past 2**62 falls back (Python ints are unbounded); a decimal
    quotient is taken in float64 only while both scaled operands are
    exact there (below 2**53);
  * division/modulo by zero raises in Python but yields inf/nan/0 in
    numpy — numeric stages run under ``errstate(divide="raise",
    invalid="raise")`` and the FloatingPointError falls back, which also
    preserves the short-circuit guarantee of ``a and b`` filters (the
    row path never evaluates ``b`` on rows ``a`` excluded);
  * mixed int/float comparisons promote int64 -> float64 in numpy but
    compare exactly in Python — ints past 2**53 fall back;
  * float group sums fold with first-occurrence initialization
    (``acc = vals[first]`` then ordered ``np.add.at``) so -0.0 and the
    fold order match the row path's left fold; float min/max fall back
    per-slot when NaN is present (Python's min/max keep the FIRST value
    on NaN, numpy propagates or ignores it).

In fact the fused operator treats any exception from a vectorized chunk
as a fallback signal and re-runs the chunk through the row closures, so
a divergence can only ever cost speed, never correctness; the running
task counts the chunks it ran (``fused_chunks``) and those it re-ran
(``fused_row_fallback_chunks``). The one
exception is ``DeviceBackendError``: a failure of the jax backend
(import, compile or run) surfaces to the caller instead of quietly
becoming a host answer.
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.core import spans
from repro.core.shuffle import KVBatch
from repro.sql import csvscan

_NUMERIC = ("int", "float")
#: int results whose float64 shadow exceeds this may be near the int64
#: wrap point (float error cannot bridge the 2**62..2**63 gap)
_INT_GUARD = float(2**62)
#: ints beyond 2**53 lose precision as float64 — exact mixed comparison
#: requires falling back to Python's exact int/float comparison
_EXACT_F64 = float(2**53)
#: the day numbers Python's datetime.date can hold
_MIN_DAY = 1 - 719163
_MAX_DAY = 3652059 - 719163


class VectorizeUnsupported(Exception):
    """Raised at COMPILE time: this expression has no vectorized form
    (udf, non-scalar operand) — the lowering keeps the row closures and
    explain() marks the operator ``[row-fallback: ...]``."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class VectorFallback(Exception):
    """Raised at RUN time, per chunk: the data hit a case where the array
    path would diverge from row semantics (int64 overflow risk, ints past
    2**53 in a float comparison, non-conforming input rows). The fused
    operator re-runs just that chunk through the bound row closures."""


class DeviceBackendError(RuntimeError):
    """The jax backend (vector_backend="jax") failed to import, compile or
    run a grouped sum. The fused operator re-raises it rather than re-run
    the chunk on the host, so a broken device path cannot hide."""


# ---------------------------------------------------------- column helpers


def to_list(col, n: int) -> list:
    """Materialize a column as a list of exact Python values."""
    if isinstance(col, np.ndarray):
        return col.tolist()  # yields Python int/float/bool
    if isinstance(col, list):
        return col
    return [col] * n  # broadcast scalar


def _elems(col, n: int):
    """Iterable view for elementwise Python loops (str ops)."""
    if isinstance(col, np.ndarray):
        return col.tolist()
    if isinstance(col, list):
        return col
    return itertools.repeat(col, n)


def _is_scalar(col) -> bool:
    return not isinstance(col, (np.ndarray, list))


def _as_float(col):
    if isinstance(col, np.ndarray):
        return col if col.dtype == np.float64 else col.astype(np.float64)
    return float(col)


# --------------------------------------------------------------- compiler


def compile_expr(expr, schema):
    """Vectorized sibling of ``Expr.bind``: expr -> fn(cols, n) -> column.
    Raises VectorizeUnsupported for udfs and non-scalar operands."""
    from repro.sql import expr as E  # local import: expr imports us lazily

    if isinstance(expr, E.Alias):
        return compile_expr(expr.child, schema)
    if isinstance(expr, E.Col):
        i = schema.index(expr.name)
        return lambda cols, n: cols[i]
    if isinstance(expr, E.Lit):
        v = expr.internal
        return lambda cols, n: v
    if isinstance(expr, E.BinOp):
        return _compile_binop(expr, schema)
    if isinstance(expr, E.Not):
        f = compile_expr(expr.child, schema)
        return lambda cols, n: _not(f(cols, n))
    if isinstance(expr, E.Substr):
        f = compile_expr(expr.child, schema)
        lo = expr.start - 1
        hi = lo + expr.length

        def f_substr(cols, n):
            v = f(cols, n)
            if _is_scalar(v):
                return v[lo:hi]
            return [s[lo:hi] for s in _elems(v, n)]
        return f_substr
    if isinstance(expr, E.Cast):
        return _compile_cast(expr, schema)
    if isinstance(expr, E.Udf):
        raise VectorizeUnsupported("udf")
    raise VectorizeUnsupported(type(expr).__name__)


def _not(v):
    if _is_scalar(v):
        return not v
    return ~np.asarray(v)


def _times(v, m: int):
    """``v * m`` for a scale alignment, exact or VectorFallback."""
    if m == 1:
        return v
    if _is_scalar(v):
        return v * m
    if np.any(np.abs(v.astype(np.float64)) * float(m) > _INT_GUARD):
        raise VectorFallback("int64 overflow risk in a decimal rescale")
    return v * m


def _add_months(days, months: int):
    """``repro.sql.expr.shift_date(d, months, 0)`` of a day-number
    array: the same month arithmetic, clamped to the month's end."""
    if (np.any(days < _MIN_DAY) or np.any(days > _MAX_DAY)):
        raise VectorFallback("date outside Python's range")
    d = days.astype("datetime64[D]")
    month = d.astype("datetime64[M]")
    dom = (d - month.astype("datetime64[D]")).astype(np.int64)
    target = month + months
    start = target.astype("datetime64[D]")
    length = ((target + 1).astype("datetime64[D]") - start).astype(np.int64)
    out = (start + np.minimum(dom, length - 1)).astype(np.int64)
    if np.any(out < _MIN_DAY) or np.any(out > _MAX_DAY):
        raise VectorFallback("date outside Python's range")
    return out


def _compile_interval(expr, schema, lt, rt):
    from repro.sql import expr as E

    date_side, iv = ((expr.left, expr.right) if lt == "date"
                     else (expr.right, expr.left))
    if not isinstance(iv, E.Interval):
        raise VectorizeUnsupported("interval")
    sign = expr.interval_sign(lt, rt)
    months, days = sign * iv.months, sign * iv.days
    f = compile_expr(date_side, schema)

    def f_shift(cols, n):
        v = f(cols, n)
        if _is_scalar(v):
            return E.shift_date(v, months, days)
        if months:
            v = _add_months(v, months)
        return v + days if days else v
    return f_shift


def _compile_binop(expr, schema):
    from repro.sql import expr as E

    lt, rt = expr.left.dtype(schema), expr.right.dtype(schema)
    if "interval" in (lt, rt):
        return _compile_interval(expr, schema, lt, rt)
    lf = compile_expr(expr.left, schema)
    rf = compile_expr(expr.right, schema)
    op = expr.op
    # int, date and decimal operands are int64 columns; + - and the
    # comparisons first bring two decimals to one scale
    exact = E.int_backed(lt) and E.int_backed(rt)
    ma, mb = expr.scales(schema)

    if op in ("and", "or"):
        # both operands evaluate EAGERLY here; the row path short-circuits.
        # Any case where the unguarded operand would misbehave (divide by
        # zero, overflow) raises out of the array op and the chunk falls
        # back to the short-circuiting row closures — so eager evaluation
        # is only ever a fast path, never a semantic change.
        def f_bool(cols, n, _and=(op == "and")):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return (a and b) if _and else (a or b)
            return (a & b) if _and else (a | b)
        return f_bool

    if op == "+" and lt == rt == "str":
        def f_concat(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return a + b
            return [x + y for x, y in zip(_elems(a, n), _elems(b, n))]
        return f_concat

    if op in ("+", "-", "*", "/", "%"):
        both_int = exact and op != "/"
        decimal_div = op == "/" and exact and (lt != "int" or rt != "int")
        if decimal_div:
            ma, mb = 10**E.exact_scale(rt), 10**E.exact_scale(lt)
        npop = {"+": np.add, "-": np.subtract, "*": np.multiply,
                "/": np.divide, "%": np.mod}[op]
        pyop = {"+": lambda a, b: a + b, "-": lambda a, b: a - b,
                "*": lambda a, b: a * b, "/": lambda a, b: a / b,
                "%": lambda a, b: a % b}[op]

        def f_arith(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return pyop(a * ma, b * mb)  # exact Python semantics
            if both_int or decimal_div:
                a, b = _times(a, ma), _times(b, mb)
            if decimal_div:
                # float64 division is correctly rounded, as Python's
                # int / int, while both operands are exact doubles
                if (np.any(np.abs(_as_float(a)) > _EXACT_F64)
                        or np.any(np.abs(_as_float(b)) > _EXACT_F64)):
                    raise VectorFallback("decimal quotient past 2**53")
                return npop(_as_float(a), _as_float(b))
            if both_int:
                r = npop(a, b)  # int64 — may have wrapped silently
                if op in ("+", "-", "*"):
                    shadow = npop(_as_float(a), _as_float(b))
                    if np.any(np.abs(shadow) > _INT_GUARD):
                        raise VectorFallback("int64 overflow risk")
                return r
            # float result: int operands promote via exact int64->float64
            return npop(_as_float(a) if lt == "int" else a,
                        _as_float(b) if rt == "int" else b)
        return f_arith

    # comparisons
    npop = {"=": np.equal, "!=": np.not_equal, "<": np.less,
            "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}
    pyop = {"=": lambda a, b: a == b, "!=": lambda a, b: a != b,
            "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
            ">": lambda a, b: a > b, ">=": lambda a, b: a >= b}
    if lt in _NUMERIC and rt in _NUMERIC:
        mixed = lt != rt
        cmp_np, cmp_py = npop[expr.op], pyop[expr.op]

        def f_numcmp(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return cmp_py(a, b)
            if mixed:
                # int64 -> float64 promotion is lossy past 2**53; Python
                # compares int vs float EXACTLY
                iv = a if lt == "int" else b
                if np.any(np.abs(np.asarray(iv, dtype=np.float64))
                          > _EXACT_F64):
                    raise VectorFallback("int past 2**53 in float compare")
            return cmp_np(a, b)
        return f_numcmp
    if exact:  # dates, decimals at one scale
        cmp_np, cmp_py = npop[expr.op], pyop[expr.op]

        def f_exactcmp(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return cmp_py(a * ma, b * mb)
            return cmp_np(_times(a, ma), _times(b, mb))
        return f_exactcmp
    if lt == rt == "bool":
        cmp_np, cmp_py = npop[expr.op], pyop[expr.op]

        def f_boolcmp(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return cmp_py(a, b)
            return cmp_np(a, b)
        return f_boolcmp
    if lt == rt == "str":
        cmp_py = pyop[expr.op]

        def cmp_nn(x, y):
            # None (NULL from outer-join padding) must yield NULL, but
            # Python's ==/!= on None return a bool — punt to row closures
            if x is None or y is None:
                raise VectorFallback("NULL in str comparison")
            return cmp_py(x, y)

        def f_strcmp(cols, n):
            a, b = lf(cols, n), rf(cols, n)
            if _is_scalar(a) and _is_scalar(b):
                return cmp_py(a, b)
            return np.fromiter((cmp_nn(x, y) for x, y in
                                zip(_elems(a, n), _elems(b, n))),
                               dtype=np.bool_, count=n)
        return f_strcmp
    raise VectorizeUnsupported(f"compare {lt}/{rt}")


_BASIC = ("int", "float", "str", "bool")


def _compile_cast(expr, schema):
    f = compile_expr(expr.child, schema)
    src = expr.child.dtype(schema)
    to = expr.to
    if src.startswith("list:"):
        raise VectorizeUnsupported("cast from list")
    if src not in _BASIC or to not in _BASIC:
        return _compile_exact_cast(f, src, to)

    def g(cols, n):
        v = f(cols, n)
        if _is_scalar(v):
            return {"int": int, "float": float, "str": str, "bool": bool}[to](v)
        if to == src:
            return v  # passthrough keeps None as NULL, same as the row path
        if src == "str" and any(x is None for x in v):
            # str(None)/bool(None) would produce a value where the row
            # path now yields NULL — only arrays-free columns carry None
            raise VectorFallback("NULL in str column cast")
        if to == "int":
            if src == "float":
                arr = np.asarray(v)
                # Python int(f) is exact and unbounded; astype(int64) is
                # only exact for finite values inside the int64 range
                if (not np.all(np.isfinite(arr))
                        or np.any(arr >= float(2**63))
                        or np.any(arr < -float(2**63))):
                    raise VectorFallback("float->int out of int64 range")
                return arr.astype(np.int64)
            if src == "bool":
                return np.asarray(v).astype(np.int64)
            # str: Python parse (may exceed int64 -> numpy refuses -> the
            # chunk falls back and the row path returns the big int)
            return np.array([int(s) for s in v], dtype=np.int64)
        if to == "float":
            if src in ("int", "bool"):
                return np.asarray(v).astype(np.float64)
            return np.fromiter(map(float, v), dtype=np.float64, count=n)
        if to == "str":
            return [str(x) for x in to_list(v, n)]
        # to bool: Python truth — nonzero numbers / nonempty strings
        if src in ("int", "float"):
            return np.asarray(v) != 0  # NaN != 0 is True, matching bool(nan)
        return np.fromiter(map(bool, v), dtype=np.bool_, count=n)
    return g


def _compile_exact_cast(f, src, to):
    """A cast to or from a date or decimal: the row path's cast on each
    value (``repro.sql.expr.cast_fn``), so both paths agree by
    construction; an int past int64 falls back."""
    from repro.sql import expr as E

    caster = E.cast_fn(src, to)
    np_type = (np.int64 if E.int_backed(to) else
               {"float": np.float64, "bool": np.bool_}.get(to))

    def g(cols, n):
        v = f(cols, n)
        if _is_scalar(v):
            return caster(v)
        if src == to:
            return v
        vals = to_list(v, n)
        if any(x is None for x in vals):
            raise VectorFallback("NULL in a cast")
        out = [caster(x) for x in vals]
        return out if np_type is None else np.array(out, dtype=np_type)
    return g


# ------------------------------------------------------------- ingestion


def scan_ingest(specs):
    """Vectorized CSV parse: ``specs`` is [(field_idx, dtype, cast_fn)]
    per pruned output column. An ASCII chunk whose lines all hold the same
    number of fields parses column-wise from one byte buffer; any other
    chunk takes the per-line parse (``repro.sql.csvscan``). Both give
    Python-identical values. The running task counts the chunks each path
    took (``ingest_columnar_chunks`` / ``ingest_line_chunks``)."""
    width = max(idx for idx, _, _ in specs) + 1 if specs else 0

    def ingest(lines):
        cols = csvscan.parse_columnar(lines, specs, width)
        if cols is None:
            _count_chunk("ingest_line_chunks")
            return csvscan.parse_lines(lines, specs), len(lines)
        _count_chunk("ingest_columnar_chunks")
        return cols, len(lines)
    return ingest


def _count_chunk(name: str) -> None:
    from repro.core.executors import task_stats
    stats = task_stats()
    if stats is not None:
        stats[name] = stats.get(name, 0) + 1


def rows_ingest(dtypes):
    """Columnize a chunk of already-materialized rows, checking exact
    concrete types (bool is not int, 1.0 is not 1 — same conformance rule
    as the wire format). Non-conforming chunks fall back to row closures.
    Dates and decimals arrive as the engine holds them, ints."""
    from repro.sql.expr import int_backed

    dtypes = ["int" if int_backed(t) else t for t in dtypes]

    def ingest(rows):
        n = len(rows)
        cols = []
        for j, dtype in enumerate(dtypes):
            vals = [r[j] for r in rows]
            if dtype == "int":
                if not all(type(v) is int for v in vals):
                    raise VectorFallback("non-int value in int column")
                cols.append(np.array(vals, dtype=np.int64))  # may overflow
            elif dtype == "float":
                if not all(type(v) is float for v in vals):
                    raise VectorFallback("non-float value in float column")
                cols.append(np.array(vals, dtype=np.float64))
            elif dtype == "bool":
                if not all(type(v) is bool for v in vals):
                    raise VectorFallback("non-bool value in bool column")
                cols.append(np.array(vals, dtype=np.bool_))
            else:  # str / list:* stay Python lists (ragged-safe)
                cols.append(vals)
        return cols, n
    return ingest


# ----------------------------------------------------------- fused stages


def filter_stage(pred_fn):
    def stage(cols, n):
        mask = pred_fn(cols, n)
        if _is_scalar(mask):
            if mask:
                return cols, n
            return [c[:0] if isinstance(c, (np.ndarray, list)) else c
                    for c in cols], 0
        kept = int(mask.sum())
        ml = None
        out = []
        for c in cols:
            if isinstance(c, np.ndarray):
                out.append(c[mask])
            elif isinstance(c, list):
                if ml is None:
                    ml = mask.tolist()
                out.append([v for v, m in zip(c, ml) if m])
            else:
                out.append(c)
        return out, kept
    return stage


def project_stage(fns):
    def stage(cols, n):
        return [f(cols, n) for f in fns], n
    return stage


# ------------------------------------------------------------- emissions


def rows_emit(cols, n):
    lists = [to_list(c, n) for c in cols]
    return list(zip(*lists)) if lists else []


def col_selector(i):
    """Vectorized sibling of ``operator.itemgetter(i)`` over columns."""
    return lambda cols, n: cols[i]


def make_kv_plain_emit(key_fns, rest_idx, kschema, vschema):
    """Join/groupByKey map side: (key-tuple, rest-tuple) records carried
    column-major so the shuffle writer packs without transposing.
    ``key_fns`` are compiled column closures (keys may be computed)."""
    def emit(cols, n):
        if n == 0:
            return []
        kcols = [to_list(f(cols, n), n) for f in key_fns]
        vcols = [to_list(cols[i], n) for i in rest_idx]
        return [KVBatch(kcols, vcols, kschema, vschema)]
    return emit


def make_kv_agg_emit(key_fns, slot_fns, slot_ops, backend):
    """Partial aggregation: group the batch by key and fold each slot
    column, emitting one (key, partials) record per distinct key in
    FIRST-OCCURRENCE order — the same order the row path's combine dict
    discovers keys, so writer flush boundaries and wire bodies match."""
    def emit(cols, n):
        key_cols = [f(cols, n) for f in key_fns]
        slot_cols = [f(cols, n) for f in slot_fns]
        return grouped_records(key_cols, slot_cols, slot_ops, n, backend)
    return emit


def grouped_records(key_cols, slot_cols, slot_ops, n, backend="numpy"):
    if n == 0:
        return []
    if key_cols:
        keys = list(zip(*[to_list(c, n) for c in key_cols]))
        index: dict = {}
        gids = np.empty(n, dtype=np.int64)
        first = []
        for i, k in enumerate(keys):
            g = index.get(k)
            if g is None:
                g = len(index)
                index[k] = g
                first.append(i)
            gids[i] = g
        uniq = list(index)  # insertion order == first occurrence
    else:  # a global aggregate: one group
        gids = np.zeros(n, dtype=np.int64)
        first, uniq = [0], [()]
    ng = len(uniq)
    first_arr = np.array(first, dtype=np.int64)
    # a column that several slots fold alike (sum(x) beside avg(x)) is
    # folded once
    folded: dict = {}
    if backend == "jax":
        device = {id(c): c for op, c in zip(slot_ops, slot_cols)
                  if op == "sum" and isinstance(c, np.ndarray)
                  and c.dtype == np.int64}
        if device:
            sums = _jax_int_sums(list(device.values()), gids, ng)
            if sums is not None:
                folded.update((("sum", i), s.tolist())
                              for i, s in zip(device, sums))
    out_slots = []
    for op, c in zip(slot_ops, slot_cols):
        key = (op, id(c))
        if key not in folded:
            folded[key] = to_list(
                _fold_slot(op, c, gids, ng, first_arr, n), ng)
        out_slots.append(folded[key])
    return [(k, tuple(s[g] for s in out_slots))
            for g, k in enumerate(uniq)]


def _fold_slot(op, col, gids, ng, first, n):
    """Fold one slot column per group on the host, reproducing the row
    path's left fold exactly: init from the group's FIRST value,
    accumulate the rest in row order (np.<op>.at applies
    sequentially)."""
    if _is_scalar(col):
        if op == "sum" and type(col) is int:
            counts = np.bincount(gids, minlength=ng)
            if abs(col) * n <= 2**62:
                return counts * col  # exact: repeated int addition
            return _py_fold(op, [col] * n, gids, ng)
        col = np.array([col] * n) if type(col) is not str else [col] * n
    if isinstance(col, list):  # str / list: columns — Python fold
        return _py_fold(op, col, gids, ng)
    if op == "sum":
        if col.dtype == np.int64:
            # bound the worst-case partial: if even the sum of |v| stays
            # far from the wrap point, int64 accumulation is exact
            if float(np.abs(col).astype(np.float64).sum()) > _INT_GUARD:
                return _py_fold(op, col.tolist(), gids, ng)
            acc = np.zeros(ng, dtype=np.int64)
            np.add.at(acc, gids, col)
            return acc
        if col.dtype == np.bool_:
            raise VectorFallback("sum over bool column")
        acc = col[first].copy()  # float: -0.0-exact first-value init
        rest = np.ones(n, dtype=np.bool_)
        rest[first] = False
        np.add.at(acc, gids[rest], col[rest])
        return acc
    if op in ("min", "max"):
        if col.dtype == np.float64 and np.isnan(col).any():
            # Python's min/max keep the FIRST operand on NaN; numpy
            # either propagates (minimum) or ignores (fmin) it
            return _py_fold(op, col.tolist(), gids, ng)
        acc = col[first].copy()
        rest = np.ones(n, dtype=np.bool_)
        rest[first] = False
        ufunc = np.minimum if op == "min" else np.maximum
        ufunc.at(acc, gids[rest], col[rest])
        return acc
    raise VectorFallback(f"slot op {op!r}")


def _py_fold(op, vals, gids, ng):
    import operator as _op
    fold = {"sum": _op.add, "min": min, "max": max}[op]
    acc = [None] * ng
    seen = [False] * ng
    for g, v in zip(gids.tolist() if isinstance(gids, np.ndarray) else gids,
                    vals):
        if seen[g]:
            acc[g] = fold(acc[g], v)
        else:
            acc[g] = v
            seen[g] = True
    return acc


def _jax_int_sums(cols, gids, ng):
    """Route a chunk's int64 group sums through the kernels/ backend
    (vector_backend="jax") as ONE grouped sum: the K columns stacked,
    column j's rows in buckets j * ng + group. Integer addition is
    associative, so an order-free segment sum is exact as long as it
    cannot overflow — the same magnitude bound as the numpy path.
    Returns a (K, ng) array, or None past that bound, where the numpy
    path's folds take over; every backend failure raises
    DeviceBackendError. Counts land in the running task's stats."""
    from repro.core.executors import task_stats
    k = len(cols)
    try:
        from repro.kernels.ops import grouped_reduce
        vals = cols[0] if k == 1 else np.concatenate(cols)
        ids = gids if k == 1 else np.concatenate(
            [gids + j * ng for j in range(k)])
        out = grouped_reduce(vals, ids, k * ng, columns=k,
                             stats=task_stats())
    except Exception as e:
        raise DeviceBackendError(
            f"jax grouped sum over {len(gids)} rows x {k} columns / {ng} "
            f"groups failed: {type(e).__name__}: {e}") from e
    return None if out is None else np.asarray(out).reshape(k, ng)


# ---------------------------------------------------------- fused operator


def make_fused(ingest, stages, emit, row_chain, batch_rows):
    """Build the batch-in/batch-out fused operator for RDD.mapBatches:
    chunk the partition iterator, run ingest -> stages -> emit per chunk
    under strict float error traps, and re-run any chunk that raises
    (other than DeviceBackendError) through ``row_chain`` (the exact
    per-row closure pipeline for the same plan segment). Emissions are
    materialized per chunk BEFORE yielding so a mid-chunk fallback never
    double-emits. A chunk's pull, parse and operator work run under the
    ``flint.scan``, ``flint.ingest`` and ``flint.fused`` spans; the
    running task counts every chunk (``fused_chunks``) and each one
    re-run on the row path (``fused_row_fallback_chunks``)."""
    def fused(it):
        it = iter(it)
        while True:
            with spans.span("flint.scan"):
                chunk = list(itertools.islice(it, batch_rows))
            if not chunk:
                return
            _count_chunk("fused_chunks")
            try:
                with np.errstate(divide="raise", invalid="raise",
                                 over="ignore", under="ignore"):
                    with spans.span("flint.ingest"):
                        cols, n = ingest(chunk)
                    with spans.span("flint.fused"):
                        for stage in stages:
                            cols, n = stage(cols, n)
                        out = emit(cols, n)
            except DeviceBackendError:
                raise
            except Exception:
                _count_chunk("fused_row_fallback_chunks")
                with spans.span("flint.fused"):
                    out = list(row_chain(iter(chunk)))
            yield from out
    return fused
