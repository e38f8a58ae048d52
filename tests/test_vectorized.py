"""Property-based equivalence for the vectorized compiler
(repro.sql.vectorized vs the bound row closures), engine-level A/B runs
with vectorization on/off, the fusion plumbing (mapBatches in the
lineage, explain markers), and a chaos leg proving fault schedules stay
invisible with the columnar map side live.

The contract under test: wherever the vectorized path PRODUCES values,
they are bit-identical (exact concrete types, -0.0 and NaN included) to
what the row closures produce; wherever it cannot guarantee that, it
raises and the fused operator re-runs the chunk through the row
closures — so the only legal divergence is an exception."""

import math
import os
import random
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import FaultPlan, FlintConfig, FlintContext
from repro.core import rdd as R
from repro.core.shuffle import iter_records
from repro.sql import (Schema, avg_, col, collect_list, count_, lit, max_,
                       min_, sum_, udf)
from repro.sql import expr as E
from repro.sql import vectorized as V
from repro.sql.lower import lower

CHAOS_SEED = int(os.environ.get("FLINT_CHAOS_SEED", "0"))

SCHEMA = Schema([("i1", "int"), ("i2", "int"), ("f1", "float"),
                 ("f2", "float"), ("b1", "bool"), ("s1", "str")])
DTYPES = [t for _, t in SCHEMA.fields]

_INT_POOL = [0, 1, -1, 7, -13, 2**31, 2**53 - 1, 2**53 + 1, 2**62,
             -2**62, 2**63 - 1, -2**63]
_FLOAT_POOL = [0.0, -0.0, 1.5, -2.25, 1e300, -1e300, 1e-300,
               float("nan"), float("inf"), float("-inf"), 2.0**53]
_STR_POOL = ["", "a", "credit", "cash", "é世界", "2015-01-02 03:04:00",
             "x" * 40, "\t", "naïve"]


def _rand_row(rng):
    return (rng.choice(_INT_POOL), rng.randint(-100, 100),
            rng.choice(_FLOAT_POOL), rng.uniform(-50, 50),
            rng.random() < 0.5, rng.choice(_STR_POOL))


def _rand_rows(rng):
    n = rng.choice([0, 1, 2, 7, 64])
    return [_rand_row(rng) for _ in range(n)]


def _rand_expr(rng, dtype, depth):
    """Random well-typed expression tree over SCHEMA."""
    leaves = [n for n, t in SCHEMA.fields if t == dtype]
    if depth <= 0 or rng.random() < 0.15:
        if leaves and rng.random() < 0.75:
            return E.Col(rng.choice(leaves))
        pool = {"int": [0, 1, -3, 2**40], "float": [0.0, -1.5, 2.5],
                "bool": [True, False], "str": ["", "credit", "é"]}[dtype]
        return E.Lit(rng.choice(pool))
    d = depth - 1
    r = rng.random()
    if dtype == "int":
        if r < 0.25:
            return E.Cast(_rand_expr(rng, rng.choice(
                ["float", "bool", "int"]), d), "int")
        op = rng.choice(["+", "-", "*", "%"])
        return E.BinOp(op, _rand_expr(rng, "int", d),
                       _rand_expr(rng, "int", d))
    if dtype == "float":
        if r < 0.2:
            return E.Cast(_rand_expr(rng, rng.choice(["int", "bool"]), d),
                          "float")
        if r < 0.4:
            return E.BinOp("/", _rand_expr(rng, rng.choice(["int", "float"]),
                                           d),
                           _rand_expr(rng, rng.choice(["int", "float"]), d))
        op = rng.choice(["+", "-", "*", "%"])
        sides = rng.choice([("float", "float"), ("int", "float"),
                            ("float", "int")])
        return E.BinOp(op, _rand_expr(rng, sides[0], d),
                       _rand_expr(rng, sides[1], d))
    if dtype == "bool":
        if r < 0.15:
            return E.Not(_rand_expr(rng, "bool", d))
        if r < 0.35:
            op = rng.choice(["and", "or"])
            return E.BinOp(op, _rand_expr(rng, "bool", d),
                           _rand_expr(rng, "bool", d))
        if r < 0.5:
            return E.Cast(_rand_expr(rng, rng.choice(["int", "float"]), d),
                          "bool")
        cmp_op = rng.choice(["=", "!=", "<", "<=", ">", ">="])
        kind = rng.random()
        if kind < 0.6:
            sides = rng.choice([("int", "int"), ("float", "float"),
                                ("int", "float"), ("float", "int")])
        elif kind < 0.8:
            sides = ("str", "str")
        else:
            sides = ("bool", "bool")
            cmp_op = rng.choice(["=", "!="])
        return E.BinOp(cmp_op, _rand_expr(rng, sides[0], d),
                       _rand_expr(rng, sides[1], d))
    # str
    if r < 0.3:
        return E.Substr(_rand_expr(rng, "str", d), rng.randint(1, 5),
                        rng.randint(0, 6))
    if r < 0.55:
        return E.BinOp("+", _rand_expr(rng, "str", d),
                       _rand_expr(rng, "str", d))
    return E.Cast(_rand_expr(rng, rng.choice(
        ["int", "float", "bool", "str"]), d), "str")


def _same(a, b):
    """Bit-exact scalar equality: same concrete type; floats compared by
    repr (distinguishes -0.0/0.0 and matches NaN to NaN)."""
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return repr(a) == repr(b)
    return a == b


def _assert_vec_matches_rows(expr, rows):
    rowfn = expr.bind(SCHEMA)
    row_exc = row_vals = None
    try:
        row_vals = [rowfn(r) for r in rows]
    except Exception as e:  # noqa: BLE001 — the engine surfaces any error
        row_exc = e
    try:
        vfn = expr.bind_vec(SCHEMA)
    except V.VectorizeUnsupported:
        return  # lowering keeps the row closures: nothing to compare
    ingest = V.rows_ingest(DTYPES)
    try:
        with np.errstate(divide="raise", invalid="raise",
                         over="ignore", under="ignore"):
            cols, n = ingest(rows)
            out = V.to_list(vfn(cols, n), n)
    except Exception:  # noqa: BLE001 — fused op re-runs via row closures
        return
    assert row_exc is None, (f"vectorized produced values where the row "
                             f"path raised {row_exc!r}: {expr.sql()}")
    assert len(out) == len(row_vals)
    for a, b in zip(row_vals, out):
        assert _same(a, b), (expr.sql(), a, b)


@given(seed=st.integers(min_value=0, max_value=2**31))
@settings(max_examples=120, deadline=None)
def test_random_expression_trees_match_row_path(seed):
    """Random expr trees x random batches (NaN/inf floats, ints past
    2**53/2**62, utf8 and empty strings, empty batches): the vectorized
    compile either matches bind() exactly or raises (-> row fallback)."""
    rng = random.Random(seed)
    expr = _rand_expr(rng, rng.choice(["int", "float", "bool", "str"]),
                      rng.randint(0, 3))
    _assert_vec_matches_rows(expr, _rand_rows(rng))


@given(seed=st.integers(min_value=0, max_value=2**31))
@settings(max_examples=60, deadline=None)
def test_random_filter_masks_match_row_path(seed):
    """filter_stage over random predicates: surviving rows (order, values,
    types) match the row filter — including all-false and empty masks."""
    rng = random.Random(seed)
    pred = _rand_expr(rng, "bool", rng.randint(0, 3))
    rows = _rand_rows(rng)
    rowfn = pred.bind(SCHEMA)
    try:
        expected = [r for r in rows if rowfn(r)]
    except Exception:  # noqa: BLE001
        expected = None  # row path raises; vectorized must not produce
    try:
        stage = V.filter_stage(pred.bind_vec(SCHEMA))
    except V.VectorizeUnsupported:
        return
    try:
        with np.errstate(divide="raise", invalid="raise",
                         over="ignore", under="ignore"):
            cols, n = V.rows_ingest(DTYPES)(rows)
            out_cols, kept = stage(cols, n)
            got = V.rows_emit(out_cols, kept)
    except Exception:  # noqa: BLE001
        return
    assert expected is not None
    assert len(got) == len(expected)
    for ra, rb in zip(expected, got):
        assert all(_same(a, b) for a, b in zip(ra, rb)), (pred.sql(), ra, rb)


# ------------------------------------------------------ grouped aggregation


def _ref_fold(op, keys, vals):
    import operator as _op
    fold = {"sum": _op.add, "min": min, "max": max}[op]
    acc = {}
    for k, v in zip(keys, vals):
        acc[k] = fold(acc[k], v) if k in acc else v
    return acc  # dict preserves first-occurrence order


@given(seed=st.integers(min_value=0, max_value=2**31))
@settings(max_examples=80, deadline=None)
def test_grouped_fold_matches_row_fold(seed):
    """grouped_records vs the row path's per-key dict fold: key order is
    first-occurrence, every slot value is bit-exact — across int columns
    near the overflow guard, float columns with NaN/-0.0, str min/max,
    and both kernels backends (numpy always; jax when importable)."""
    try:
        import jax  # noqa: F401
        backends = ["numpy", "jax"]
    except Exception:  # pragma: no cover - jax is present in this image
        backends = ["numpy"]
    rng = random.Random(seed)
    backend = rng.choice(backends)
    n = rng.choice([0, 1, 5, 40])
    key_vals = [(rng.randint(0, 4), rng.choice(["a", "b", "é"]))
                for _ in range(n)]
    slot_ops, slot_cols, ref_cols = [], [], []
    for _ in range(rng.randint(1, 3)):
        kind = rng.choice(["int", "bigint", "float", "str"])
        if kind == "str":
            op = rng.choice(["min", "max"])
            vals = [rng.choice(_STR_POOL) for _ in range(n)]
            colv = list(vals)
        else:
            op = rng.choice(["sum", "min", "max"])
            if kind == "int":
                vals = [rng.randint(-1000, 1000) for _ in range(n)]
                colv = np.array(vals, dtype=np.int64)
            elif kind == "bigint":
                vals = [rng.choice([2**61, -2**61, 2**62, 5])
                        for _ in range(n)]
                colv = np.array(vals, dtype=np.int64)
            else:
                vals = [rng.choice(_FLOAT_POOL) for _ in range(n)]
                colv = np.array(vals, dtype=np.float64)
        slot_ops.append(op)
        slot_cols.append(colv)
        ref_cols.append(vals)
    kcols = [np.array([k[0] for k in key_vals], dtype=np.int64),
             [k[1] for k in key_vals]]
    try:
        with np.errstate(divide="raise", invalid="raise",
                         over="ignore", under="ignore"):
            got = list(iter_records(
                V.grouped_records(kcols, slot_cols, slot_ops, n, backend)))
    except FloatingPointError:
        return  # inf/-inf collisions etc.: the fused op re-runs row-wise
    refs = [_ref_fold(op, key_vals, vals)
            for op, vals in zip(slot_ops, ref_cols)]
    ref_keys = list(refs[0]) if refs and n else []
    assert [k for k, _ in got] == ref_keys
    for k, partials in got:
        for slot, ref in zip(partials, refs):
            assert _same(slot, ref[k]), (slot_ops, k, slot, ref[k])


@pytest.mark.parametrize("zeros", [(0.0, -0.0), (-0.0, 0.0)],
                         ids=["plus-first", "minus-first"])
@pytest.mark.parametrize("op", ["min", "max"])
def test_grouped_min_max_keep_the_first_of_equal_zeros(op, zeros):
    """-0.0 == 0.0: Python's min/max keep the first operand, as the row
    path's fold does; numpy's minimum/maximum may return either."""
    vals = [2.5, zeros[0], zeros[1], 2.5] if op == "min" else \
        [-2.5, zeros[0], zeros[1], -2.5]
    got = list(iter_records(V.grouped_records(
        [np.zeros(4, dtype=np.int64)], [np.array(vals)], [op], 4)))
    want = _ref_fold(op, [0] * 4, vals)[0]
    assert [k for k, _ in got] == [(0,)] and _same(got[0][1][0], want)


# ------------------------------------------------------------- engine A/B


def _mk_ctx(vectorize, **kw):
    kw.setdefault("concurrency", 4)
    return FlintContext(config=FlintConfig(vectorize=vectorize, **kw))


TAXI = Schema([("pickup", "str"), ("payment", "str"), ("tip", "float"),
               ("total", "float"), ("miles", "float")])


def _taxi_csv(n=400):
    return "".join(
        f"2015-01-0{1 + i % 9} 0{i % 10}:1{i % 5}:00,"
        f"{'credit' if i % 3 else 'cash'},{i % 7}.25,{i * 1.5},{i % 11}.0\n"
        for i in range(n))


def _sql_job(ctx):
    ctx.upload("t.csv", _taxi_csv().encode())
    df = ctx.read_csv("t.csv", TAXI, 4)
    q = (df.withColumn("hour", col("pickup").substr(12, 2))
           .withColumn("cents", (col("tip") * lit(100.0)).cast("int"))
           .where(col("payment") == lit("credit"))
           .groupBy("hour")
           .agg(sum_(col("cents")).alias("tips"), count_().alias("n"),
                avg_(col("total")).alias("avg_total"),
                min_(col("miles")).alias("min_miles")))
    r1 = sorted(q.collect())
    a = df.groupBy("payment").agg(count_().alias("n"))
    b = df.groupBy("payment").agg(sum_(col("tip")).alias("s"))
    r2 = sorted(a.join(b, on="payment").collect())
    r3 = sorted(df.groupBy("payment")
                .agg(collect_list(col("miles")).alias("ms"),
                     max_(col("total")).alias("mt")).collect())
    return r1, r2, r3


def _exact_rows(xs, ys):
    assert len(xs) == len(ys)
    for rx, ry in zip(xs, ys):
        assert len(rx) == len(ry)
        for a, b in zip(rx, ry):
            if isinstance(a, list):
                assert type(b) is list and len(a) == len(b)
                assert all(_same(x, y) for x, y in zip(a, b))
            else:
                assert _same(a, b), (rx, ry)


def test_engine_ab_vectorized_matches_row_path():
    """Scan->filter->project->agg, join-of-aggregates, and collect_list
    groupBy: vectorize=True and vectorize=False collect identical rows
    with identical concrete types."""
    _exact_rows_all = zip(_sql_job(_mk_ctx(True)), _sql_job(_mk_ctx(False)))
    for vec, row in _exact_rows_all:
        _exact_rows(vec, row)


def test_engine_ab_small_batches_force_chunk_boundaries():
    """vector_batch_rows=7 puts chunk boundaries (and cross-chunk partial
    merging) in play; results still match the row path exactly."""
    for vec, row in zip(_sql_job(_mk_ctx(True, vector_batch_rows=7)),
                        _sql_job(_mk_ctx(False))):
        _exact_rows(vec, row)


def test_engine_ab_empty_and_all_false_filter():
    for vectorize in (True, False):
        ctx = _mk_ctx(vectorize)
        df = (ctx.parallelize([(i, float(i)) for i in range(20)], 3)
              .toDF([("k", "int"), ("v", "float")]))
        assert (df.where(col("k") > lit(10**6))
                .groupBy("k").agg(sum_(col("v")).alias("s"))
                .collect()) == []
        empty = (ctx.parallelize([], 2)
                 .toDF([("k", "int"), ("v", "float")]))
        assert empty.select("k").collect() == []


def test_engine_ab_utf8_and_ragged_fallback():
    """utf8 keys plus a row that breaks int64 (bigint) mid-partition:
    the chunk falls back and both paths agree."""
    rows = [("é世", 1, 2**70), ("b", 2, 5), ("é世", 3, -7), ("b", 4, 2**70)]
    out = {}
    for vectorize in (True, False):
        ctx = _mk_ctx(vectorize)
        df = (ctx.parallelize(rows, 2)
              .toDF([("s", "str"), ("k", "int"), ("v", "int")]))
        out[vectorize] = sorted(
            df.groupBy("s").agg(sum_(col("v")).alias("t"),
                                count_().alias("n")).collect())
    assert out[True] == out[False]
    _exact_rows(out[True], out[False])


def test_udf_falls_back_per_operator_and_explain_marks_it():
    ctx = _mk_ctx(True)
    df = (ctx.parallelize([(i % 3, float(i)) for i in range(30)], 2)
          .toDF([("k", "int"), ("v", "float")]))
    dbl = udf(lambda x: x * 2.0, "float", name="dbl")
    q = (df.where(col("v") > lit(2.0))
         .select("k", dbl(col("v")).alias("d"))
         .groupBy("k").agg(sum_(col("d")).alias("s")))
    plan = q.explain()
    assert "[row-fallback: udf]" in plan
    assert "[vectorized]" in plan
    row_ctx = _mk_ctx(False)
    df2 = (row_ctx.parallelize([(i % 3, float(i)) for i in range(30)], 2)
           .toDF([("k", "int"), ("v", "float")]))
    q2 = (df2.where(col("v") > lit(2.0))
          .select("k", dbl(col("v")).alias("d"))
          .groupBy("k").agg(sum_(col("d")).alias("s")))
    _exact_rows(sorted(q.collect()), sorted(q2.collect()))


def test_fusion_plants_mapbatches_in_the_lineage():
    """The lowering actually fuses: with vectorize on, the lineage below
    the shuffle is a single mapBatches narrow op (scan -> filter ->
    project -> partial agg); with it off, no mapbatches op exists."""
    def kinds(vectorize):
        ctx = _mk_ctx(vectorize)
        ctx.upload("t.csv", _taxi_csv(50).encode())
        df = ctx.read_csv("t.csv", TAXI, 2)
        q = (df.where(col("payment") == lit("credit"))
             .withColumn("hour", col("pickup").substr(12, 2))
             .groupBy("hour").agg(count_().alias("n")))
        from repro.sql.optimizer import optimize
        rdd, _, _ = lower(optimize(q.plan, ctx), ctx)
        seen = []
        node = rdd
        while node is not None:
            if isinstance(node, R.Narrow):
                seen.append(node.kind)
            node = getattr(node, "parent", None)
        return seen
    assert "mapbatches" in kinds(True)
    assert "mapbatches" not in kinds(False)


# ------------------------------------------------------ jax device backend


def _sql_workloads():
    """The two SQL taxi queries chip_smoke.py runs on the chip."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.shuffle_backends import SQL_WORKLOADS
    return SQL_WORKLOADS


@pytest.mark.parametrize("query", ["sql_filter_groupby", "sql_join_agg"])
def test_jax_backend_taxi_queries_match_numpy_and_row_path(query):
    """vector_backend="jax" sends the integer tip sums to bucket_reduce:
    the answer equals the numpy backend's and the row path's exactly, the
    kernel ran, and nothing went back to the host."""
    from repro.data.synthetic import taxi_csv

    data = taxi_csv(3000, seed=13)
    fn = _sql_workloads()[query]
    answers = {}
    for leg, kw in [("jax", dict(vector_backend="jax")),
                    ("numpy", dict(vector_backend="numpy")),
                    ("row", dict(vectorize=False))]:
        ctx = FlintContext(config=FlintConfig(concurrency=4, **kw))
        ctx.upload("taxi.csv", data)
        answers[leg] = sorted(fn(ctx))
        if leg == "jax":
            dev = ctx.last_scheduler.device_stats
            assert dev["kernel_calls"] > 0
            assert dev["device_fallbacks"] == 0 and dev["x64_sums"] == 0
    _exact_rows(answers["jax"], answers["numpy"])
    _exact_rows(answers["jax"], answers["row"])


def test_jax_backend_hands_sums_past_2_62_back_to_host():
    """Sums past the x64 envelope are the one documented hand-back: the
    numpy path's bigint fold answers, and the task counts it."""
    rows = [(i % 2, 2**61) for i in range(10)] + [(5, 3)]
    out = {}
    for vectorize in (True, False):
        ctx = _mk_ctx(vectorize, vector_backend="jax")
        df = ctx.parallelize(rows, 1).toDF([("k", "int"), ("v", "int")])
        # the filter gives the fused operator a step to compile
        out[vectorize] = sorted(
            df.where(col("k") >= lit(0))
            .groupBy("k").agg(sum_(col("v")).alias("s")).collect())
        if vectorize:
            dev = ctx.last_scheduler.device_stats
            assert dev["device_fallbacks"] == 1 and dev["kernel_calls"] == 0
    _exact_rows(out[True], out[False])
    assert out[True][0] == (0, 5 * 2**61)


@pytest.mark.parametrize("failure", ["runtime", "import"])
def test_jax_backend_device_failure_raises(monkeypatch, failure):
    """A device or import failure under vector_backend="jax" fails the
    query; it is never re-run as a numpy or row-path answer."""
    if failure == "runtime":
        from repro.kernels import ops

        def broken(*args, **kwargs):
            raise RuntimeError("TPU device lost")
        monkeypatch.setattr(ops, "grouped_reduce", broken)
    else:
        monkeypatch.setitem(sys.modules, "repro.kernels.ops", None)
    ctx = _mk_ctx(True, vector_backend="jax")
    ctx.upload("t.csv", _taxi_csv(50).encode())
    df = ctx.read_csv("t.csv", TAXI, 2)
    q = (df.withColumn("cents", (col("tip") * lit(100.0)).cast("int"))
         .groupBy("payment").agg(sum_(col("cents")).alias("c")))
    with pytest.raises(V.DeviceBackendError) as info:
        q.collect()
    cause = RuntimeError if failure == "runtime" else ImportError
    assert isinstance(info.value.__cause__, cause)


# ------------------------------------------------------------- chaos leg


def _chaos_ctx(backend, plan, vectorize=True):
    cfg = FlintConfig(shuffle_backend=backend, concurrency=8,
                      flush_records=50, visibility_timeout_s=0.5,
                      drain_timeout_s=1.5, retry_base_s=0.001,
                      retry_cap_s=0.01, max_stage_retries=5,
                      vectorize=vectorize)
    return FlintContext(config=cfg, fault_plan=plan)


def _chaos_job(ctx):
    """One fused-kv aggregation (scan->filter->partial-agg emitting
    pre-combined partials), one join whose map sides ship KVBatch
    columnar carriers, and one CHAINED multi-shuffle pipeline (two
    aggregations feeding a join — consumers that are themselves
    producers — lost-input recovery expands reopens deepest-first, see
    test_chained_multi_shuffle_recovers_deepest_lost_exchange)."""
    data = [(i % 7, i, float(i % 5)) for i in range(300)]
    df = (ctx.parallelize(data, 4)
          .toDF([("k", "int"), ("v", "int"), ("w", "float")]))
    agg = sorted(df.where(col("v") % lit(3) != lit(1))
                 .groupBy("k").agg(sum_(col("v")).alias("t"),
                                   count_().alias("n"),
                                   min_(col("w")).alias("lo")).collect())
    left = (ctx.parallelize([(i % 7, i) for i in range(100)], 4)
            .toDF([("k", "int"), ("a", "int")]))
    right = (ctx.parallelize([(i % 7, float(i)) for i in range(50)], 4)
             .toDF([("k", "int"), ("b", "float")]))
    joined = sorted(left.join(right, on="k").collect())
    chained = sorted(df.groupBy("k").agg(sum_(col("v")).alias("t"))
                     .join(right.groupBy("k").agg(count_().alias("m")),
                           on="k", numPartitions=3).collect())
    return agg, joined, chained


TRANSIENT_PREFIXES = ("_exchange/", "_spill/", "_payload/", "_result/",
                      "_stream/")


@pytest.mark.parametrize("backend", ["sqs", "s3"])
def test_chaos_vectorized_sql_is_invisible(backend):
    """Seeded fault schedules against the FUSED columnar pipeline
    (vectorized scan->filter->partial-agg plus KVBatch join map sides):
    every run returns the fault-free row-path answer and leaks nothing —
    re-emitted batches stay byte-identical so (src, seq) dedup holds."""
    expected = _chaos_job(_chaos_ctx(backend, None, vectorize=False))
    assert expected == _chaos_job(_chaos_ctx(backend, None, vectorize=True))
    for i in range(3):
        plan = FaultPlan(seed=CHAOS_SEED * 1000 + i,
                         s3_error_prob=0.03, sqs_error_prob=0.03,
                         sqs_delay_prob=0.10, sqs_delay_s=0.02,
                         invoke_throttle_prob=0.02, lose_object_prob=0.02)
        ctx = _chaos_ctx(backend, plan)
        assert _chaos_job(ctx) == expected, (backend, i)
        leaked = [k for p in TRANSIENT_PREFIXES for k in ctx.store.list(p)]
        assert not leaked, leaked[:5]
        assert ctx.last_scheduler.sqs._queues == {}


def test_chained_multi_shuffle_recovers_deepest_lost_exchange():
    """Regression for the old s3 chained-shuffle flake: the FIRST
    ``_exchange/`` object written — a pre-join aggregation's partials,
    the deepest shuffle input of the pipeline — is acknowledged and then
    lost. The middle stage is a consumer that is itself a producer;
    recovery must reopen the DEEPEST lost input (not just the
    shallowest) to reproduce the fault-free answer with no leaks."""
    def chained(ctx):
        df = (ctx.parallelize([(i % 7, i) for i in range(200)], 4)
              .toDF([("k", "int"), ("v", "int")]))
        right = (ctx.parallelize([(i % 7, float(i)) for i in range(50)], 4)
                 .toDF([("k", "int"), ("b", "float")]))
        return sorted(df.groupBy("k").agg(sum_(col("v")).alias("t"))
                      .join(right.groupBy("k")
                            .agg(count_().alias("m")),
                            on="k", numPartitions=3).collect())
    expected = chained(_chaos_ctx("s3", None))
    plan = FaultPlan(seed=CHAOS_SEED + 4242, lose_keys=("_exchange/",))
    ctx = _chaos_ctx("s3", plan)
    assert chained(ctx) == expected
    leaked = [k for p in TRANSIENT_PREFIXES for k in ctx.store.list(p)]
    assert not leaked, leaked[:5]
    assert ctx.last_scheduler.sqs._queues == {}
