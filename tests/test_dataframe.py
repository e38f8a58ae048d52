"""DataFrame correctness: a property harness running random structured
queries against a plain-Python reference evaluator across the SQS/S3 x
columnar matrix (the ISSUE-4 random-DAG pattern, lifted to the SQL
surface), plus deterministic tests for:

  * optimized == unoptimized == reference (the optimizer preserves
    semantics),
  * RDD.take(n) partial evaluation (a source task stops READING after
    its first n records; the action merge short-circuits),
  * declared-schema columnar batches (and the silent fallback when data
    outgrows the declaration),
  * adaptive transport selection on the plain-RDD path (config "auto" vs
    pinned override),
  * DataFrame.cache(), count(), cluster-backend equality.
"""

import operator
import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import FlintConfig, FlintContext, build_plan
from repro.sql import (Schema, avg_, col, collect_list, count_, lit, max_,
                       min_, sum_)

ADD = operator.add

TRANSIENT_PREFIXES = ("_spill/", "_payload/", "_exchange/", "_result/",
                      "_broadcast/", "_stream/")


def assert_no_leaks(ctx):
    for prefix in TRANSIENT_PREFIXES:
        assert not ctx.store.list(prefix), f"leaked {prefix} keys"
    assert ctx.last_scheduler.sqs._queues == {}, "queues leaked"


# --------------------------------------------------- random query specs
#
# A query is a base dataset plus a sequence of ops; the engine runs it as
# a DataFrame (optimized and not), the reference interprets the SAME ops
# over plain Python lists of tuples. Value columns stay integral so sums
# are arrival-order-independent across transports.

BASE_SCHEMA = Schema([("k", "int"), ("s", "str"), ("v", "int"),
                      ("w", "int")])
LETTERS = ["aa", "bb", "cc"]


def gen_query(seed: int):
    rng = random.Random(seed)
    rows = [(rng.randrange(4), rng.choice(LETTERS), rng.randrange(1, 9),
             rng.randrange(1, 5))
            for _ in range(rng.randint(6, 18))]
    ops = []
    n_ops = rng.randint(1, 3)
    for _ in range(n_ops):
        kind = rng.choice(["where", "withcol", "select", "group", "join"])
        ops.append((kind, rng.random()))
    if rng.random() < 0.5:
        ops.append(("sortlimit", rng.random()))
    return rows, ops


def _apply_ops(df, rows, schema_cols, ops, rng_rows2):
    """Build the DataFrame query AND its reference rows in lockstep.
    ``schema_cols`` tracks (name, dtype) of the current shape."""

    def names():
        return [n for n, _ in schema_cols]

    def idx(name):
        return names().index(name)

    for kind, r in ops:
        cols = names()
        int_cols = [n for n, t in schema_cols if t == "int"]
        if kind == "where" and int_cols:
            c = int_cols[int(r * len(int_cols)) % len(int_cols)]
            cut = int(r * 10) % 5
            df = df.where(col(c) > lit(cut))
            i = idx(c)
            rows = [row for row in rows if row[i] > cut]
        elif kind == "withcol" and int_cols:
            c = int_cols[int(r * 7) % len(int_cols)]
            new = f"x{len(cols)}"
            df = df.withColumn(new, col(c) * lit(2) + lit(1))
            i = idx(c)
            rows = [row + (row[i] * 2 + 1,) for row in rows]
            schema_cols = schema_cols + [(new, "int")]
        elif kind == "select":
            keep_n = max(1, int(r * len(cols)) % len(cols) or 1)
            keep = cols[:keep_n]
            df = df.select(*keep)
            ids = [idx(n) for n in keep]
            rows = [tuple(row[i] for i in ids) for row in rows]
            schema_cols = [schema_cols[i] for i in ids]
        elif kind == "group" and int_cols:
            key = cols[int(r * 3) % min(2, len(cols))]
            vcol = int_cols[int(r * 11) % len(int_cols)]
            ki = idx(key)
            vi = idx(vcol)
            use_list = r > 0.7
            aggs = [sum_(col(vcol)).alias("t"), count_().alias("n"),
                    min_(col(vcol)).alias("lo"),
                    avg_(col(vcol)).alias("m")]
            if use_list:
                aggs.append(collect_list(col(vcol)).alias("vs"))
            df = df.groupBy(key).agg(*aggs)
            groups: dict = {}
            for row in rows:
                groups.setdefault(row[ki], []).append(row[vi])
            rows = []
            for gk, vals in groups.items():
                out = (gk, sum(vals), len(vals), min(vals),
                       sum(vals) / len(vals))
                if use_list:
                    out = out + (vals,)
                rows.append(out)
            kt = schema_cols[ki][1]
            schema_cols = [(key, kt), ("t", "int"), ("n", "int"),
                           ("lo", "int"), ("m", "float")]
            if use_list:
                schema_cols.append(("vs", "list:int"))
        elif kind == "join":
            if "k" not in names() or any(t.startswith("list:")
                                         for _, t in schema_cols):
                continue
            if schema_cols[idx("k")][1] != "int":
                continue
            bname = f"bonus{len(cols)}"  # unique across repeated joins
            rows2 = [(i, rng_rows2.randrange(10))
                     for i in range(rng_rows2.randrange(2, 6))]
            df2 = (df.ctx.parallelize(rows2, 2)
                   .toDF([("k", "int"), (bname, "int")]))
            df = df.join(df2, on="k")
            ki = idx("k")
            right = {}
            for kk, b in rows2:
                right.setdefault(kk, []).append(b)
            out = []
            for row in rows:
                for b in right.get(row[ki], []):
                    rest = tuple(v for i, v in enumerate(row) if i != ki)
                    out.append((row[ki],) + rest + (b,))
            rows = out
            schema_cols = ([schema_cols[ki]]
                           + [f for i, f in enumerate(schema_cols)
                              if i != ki] + [(bname, "int")])
        elif kind == "sortlimit":
            sortable = [n for n, t in schema_cols
                        if not t.startswith("list:")]
            if not sortable:
                continue
            n = max(1, int(r * 6))
            df = df.orderBy(*sortable).limit(n)
            ids = [idx(c) for c in sortable]
            rows = sorted(rows,
                          key=lambda row: tuple(row[i] for i in ids))[:n]
            break  # final operators close the query
    return df, rows


def _norm(x):
    if isinstance(x, list):
        return sorted((_norm(v) for v in x), key=repr)
    if isinstance(x, tuple):
        return tuple(_norm(v) for v in x)
    return x


def canon(rows):
    return sorted(repr(_norm(r)) for r in rows)


def run_query_case(seed, backend, columnar, check_unoptimized=False):
    rows, ops = gen_query(seed)
    ctx = FlintContext("flint",
                       FlintConfig(concurrency=6, shuffle_backend=backend,
                                   columnar_batches=columnar))
    df = ctx.parallelize(rows, 2).toDF(BASE_SCHEMA)
    df, expect = _apply_ops(df, rows, list(BASE_SCHEMA.fields), ops,
                            random.Random(seed ^ 0xBEEF))
    got = df.collect()
    assert canon(got) == canon(expect), f"seed {seed}: engine != reference"
    assert_no_leaks(ctx)
    if check_unoptimized:
        raw = df.collect(optimize=False)
        assert canon(raw) == canon(expect), \
            f"seed {seed}: unoptimized lowering != reference"
        assert_no_leaks(ctx)


def _make_cell_test(backend, columnar):
    @given(seed=st.integers(min_value=0, max_value=10**9))
    @settings(max_examples=25, deadline=None)
    def test(seed):
        run_query_case(seed, backend, columnar,
                       check_unoptimized=(backend == "sqs" and columnar))
    test.__name__ = (f"test_random_df_equivalence_{backend}_"
                     f"{'columnar' if columnar else 'pickle'}")
    test.__qualname__ = test.__name__
    return test


for _cell in [(b, c) for b in ("sqs", "s3") for c in (True, False)]:
    _cell_test = _make_cell_test(*_cell)
    globals()[_cell_test.__name__] = _cell_test
del _cell, _cell_test


def run_adaptive_ab_query(seed, backend, columnar):
    """The same generated query with adaptive execution ON and OFF must
    match the reference evaluator (and each other) with zero leaks."""
    rows, ops = gen_query(seed)
    for adaptive in (True, False):
        ctx = FlintContext("flint",
                           FlintConfig(concurrency=6,
                                       shuffle_backend=backend,
                                       columnar_batches=columnar,
                                       adaptive=adaptive))
        df = ctx.parallelize(rows, 2).toDF(BASE_SCHEMA)
        df, expect = _apply_ops(df, rows, list(BASE_SCHEMA.fields), ops,
                                random.Random(seed ^ 0xBEEF))
        got = df.collect()
        assert canon(got) == canon(expect), \
            f"seed {seed} adaptive={adaptive}: engine != reference"
        assert_no_leaks(ctx)


def _make_adaptive_ab_test(backend, columnar):
    @given(seed=st.integers(min_value=0, max_value=10**9))
    @settings(max_examples=25, deadline=None)
    def test(seed):
        run_adaptive_ab_query(seed, backend, columnar)
    test.__name__ = (f"test_random_df_adaptive_ab_{backend}_"
                     f"{'columnar' if columnar else 'pickle'}")
    test.__qualname__ = test.__name__
    return test


for _cell in [(b, c) for b in ("sqs", "s3") for c in (True, False)]:
    _cell_test = _make_adaptive_ab_test(*_cell)
    globals()[_cell_test.__name__] = _cell_test
del _cell, _cell_test


# ----------------------------------------------------------- RDD.take(n)


def test_take_returns_first_records_in_partition_order():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    ctx.upload("n.txt", ("\n".join(str(i) for i in range(100)) + "\n")
               .encode())
    r = ctx.textFile("n.txt", 4).map(int)
    assert r.take(5) == [0, 1, 2, 3, 4]
    assert r.take(0) == []
    assert len(r.take(500)) == 100
    assert_no_leaks(ctx)


def test_take_stops_reading_the_source_early():
    """The limit op caps how much each source task READS, not just what
    it returns: with small fetch chunks, take(3) must move far fewer
    bytes from the store than a full collect."""
    data = ("\n".join(f"line-{i:06d}" for i in range(20_000)) + "\n")
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            chunk_fetch_bytes=2048))
    ctx.upload("big.txt", data.encode())
    rdd = ctx.textFile("big.txt", 4)
    rdd.collect()
    full_read = ctx.ledger.bytes_from_s3
    ctx2 = FlintContext("flint", FlintConfig(concurrency=4,
                                             chunk_fetch_bytes=2048))
    ctx2.upload("big.txt", data.encode())
    got = ctx2.textFile("big.txt", 4).take(3)
    assert got == ["line-000000", "line-000001", "line-000002"]
    assert ctx2.ledger.bytes_from_s3 < full_read / 10, \
        (ctx2.ledger.bytes_from_s3, full_read)


def test_take_after_shuffle_and_on_cluster():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    pairs = [(i % 5, 1) for i in range(50)]
    out = ctx.parallelize(pairs, 4).reduceByKey(ADD, 3).take(2)
    assert len(out) == 2 and all(v == 10 for _, v in out)
    cc = FlintContext("cluster", FlintConfig(concurrency=4))
    assert len(cc.parallelize(pairs, 4).reduceByKey(ADD, 3).take(2)) == 2


def test_dataframe_limit_uses_merge_short_circuit():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i, i * 2) for i in range(40)], 4)
          .toDF([("a", "int"), ("b", "int")]))
    got = df.limit(7).collect()
    assert len(got) == 7
    assert got == [(i, i * 2) for i in range(7)]  # partition order
    assert df.limit(7).count() == 7


# ----------------------------------------- adaptive transport selection


def test_auto_backend_resolves_transport_at_plan_time():
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="auto"))
    ctx.upload("small.txt", b"1\n2\n3\n")
    small = (ctx.textFile("small.txt", 2).map(lambda x: (int(x), 1))
             .reduceByKey(ADD, 2))
    plan = build_plan(small, "collect", default_transport="auto")
    assert plan[0].write.transport == "sqs"

    ctx.upload("big.bin", b"x" * 50_000_000)
    big = (ctx.textFile("big.bin", 2).map(lambda x: (x, 1))
           .reduceByKey(ADD, 2))
    plan = build_plan(big, "collect", default_transport="auto")
    assert plan[0].write.transport == "s3"
    # ShuffleRead mirrors the resolved choice so both ends agree
    read = plan[1].tasks[0].input
    assert read.transports == {plan[0].write.shuffle_id: "s3"}


def test_pinned_backend_overrides_auto_and_hints_override_both():
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="s3"))
    ctx.upload("small.txt", b"1\n2\n3\n")
    rdd = (ctx.textFile("small.txt", 2).map(lambda x: (int(x), 1))
           .reduceByKey(ADD, 2))
    plan = build_plan(rdd, "collect",
                      default_transport=ctx.config.shuffle_backend)
    assert plan[0].write.transport == ""  # runtime default applies
    hinted = (ctx.textFile("small.txt", 2).map(lambda x: (int(x), 1))
              .reduceByKey(ADD, 2, transport="sqs"))
    plan = build_plan(hinted, "collect", default_transport="auto")
    assert plan[0].write.transport == "sqs"


def test_auto_backend_runs_end_to_end():
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="auto"))
    out = sorted(ctx.parallelize([(i % 3, 1) for i in range(30)], 3)
                 .reduceByKey(ADD, 2).collect())
    assert out == [(0, 10), (1, 10), (2, 10)]
    assert_no_leaks(ctx)


def test_cached_lineage_sizes_feed_the_estimate():
    """A ready cache() materialization prices the shuffle from ACTUAL
    stored batch bytes instead of the source-size heuristic."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="auto"))
    src = ctx.parallelize([(i % 5, i) for i in range(50)], 2).cache()
    src.reduceByKey(ADD, 2).collect()  # materializes the cache
    assert ctx.store.list("_cache/")
    plan = build_plan(src.reduceByKey(ADD, 2), "collect",
                      cache_index=ctx._cache_index,
                      default_transport="auto")
    # cached bytes are tiny -> sqs; and the plan reads the cache
    assert plan[0].write.transport == "sqs"
    from repro.core.dag import CacheInput
    assert isinstance(plan[0].tasks[0].input, CacheInput)


# ------------------------------------------- declared columnar schemas


def test_lowered_shuffles_declare_batch_schemas():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(1, "a", 2)], 2)
          .toDF([("k", "int"), ("s", "str"), ("v", "int")]))
    q = df.groupBy("k").agg(sum_(col("v")).alias("t"),
                            count_().alias("n"))
    from repro.sql.lower import lower
    from repro.sql.optimizer import optimize
    rdd, _, _ = lower(optimize(q.plan, ctx), ctx)
    plan = build_plan(rdd, "collect")
    write = plan[0].write
    assert write.batch_schema == ("t(i)", "t(i,i)")

    j = df.select("k", "v").join(
        (ctx.parallelize([(1, "z")], 2)
         .toDF([("k", "int"), ("z", "str")])), on="k")
    rdd, _, _ = lower(optimize(j.plan, ctx), ctx)
    plan = build_plan(rdd, "collect")
    schemas = {w.write.key_side: w.write.batch_schema
               for w in [s for s in plan if s.write is not None]}
    assert schemas == {"left": ("t(i)", "t(i)"),
                       "right": ("t(i)", "t(s)")}


def test_declared_schema_overflow_falls_back_safely():
    """A sum outgrowing int64 violates the declared "i" column — the pack
    falls back (sniff -> pickle framing) and results stay exact."""
    big = 2**62
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i % 2, big) for i in range(8)], 2)
          .toDF([("k", "int"), ("v", "int")]))
    out = sorted(df.groupBy("k").agg(sum_(col("v")).alias("t")).collect())
    assert out == [(0, 4 * big), (1, 4 * big)]
    assert_no_leaks(ctx)


def test_grouped_lists_reshuffle_columnar():
    """collect_list output (list-typed values) re-shuffled downstream now
    rides the list codec instead of falling back to pickle framing — and
    the results are identical either way."""
    from repro.core.shuffle import is_columnar, pack_batch, unpack_batch
    # the exact record shape the second shuffle ships: (key, full row
    # containing a list column)
    records = [(i % 2, (i, [j * 7 for j in range(i + 1)], i % 2))
               for i in range(6)]
    bodies = pack_batch(records)
    assert all(is_columnar(b) for b in bodies), \
        "list-valued rows fell back to pickle framing"
    assert [r for b in bodies for r in unpack_batch(b)] == records

    rows = [(i % 6, i % 4) for i in range(600)]
    outs = []
    for columnar in (False, True):
        ctx = FlintContext("flint",
                           FlintConfig(concurrency=4,
                                       shuffle_backend="sqs",
                                       columnar_batches=columnar))
        grouped = (ctx.parallelize(rows, 3)
                   .toDF([("k", "int"), ("v", "int")])
                   .groupBy("k").agg(collect_list(col("v")).alias("vs"))
                   .withColumn("b", col("k") % lit(2)))
        out = (grouped.select("b", col("vs").alias("vs2"))
               .groupBy("b").agg(count_().alias("n")))
        got = sorted(out.collect())
        assert got == [(0, 3), (1, 3)]
        outs.append(got)
        assert_no_leaks(ctx)
    assert outs[0] == outs[1]


# ----------------------------------------------------------- misc API


def test_dataframe_cache_cuts_second_action():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i % 3, i) for i in range(30)], 2)
          .toDF([("k", "int"), ("v", "int")])
          .groupBy("k").agg(sum_(col("v")).alias("t"))
          .cache())
    first = sorted(df.collect())
    invokes = ctx.ledger.lambda_requests
    second = sorted(df.collect())
    assert first == second
    assert ctx.ledger.lambda_requests - invokes < invokes
    ctx.clear_cache()


def test_cluster_backend_matches_flint():
    rows = [(i % 4, "ab"[i % 2], i) for i in range(40)]
    outs = []
    for backend in ("flint", "cluster"):
        ctx = FlintContext(backend, FlintConfig(concurrency=4))
        df = (ctx.parallelize(rows, 3)
              .toDF([("k", "int"), ("s", "str"), ("v", "int")]))
        q = (df.where(col("v") > lit(3))
             .groupBy("k")
             .agg(sum_(col("v")).alias("t"), max_(col("s")).alias("hi")))
        outs.append(sorted(q.collect()))
    assert outs[0] == outs[1]


def test_count_matches_collect_len():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i, i) for i in range(25)], 3)
          .toDF([("a", "int"), ("b", "int")]))
    assert df.count() == 25
    assert df.where(col("a") < lit(10)).count() == 10
    assert df.count(optimize=False) == 25


def test_serde_ships_containers_of_functions():
    """The expression compiler closes over LISTS of compiled
    sub-expressions (and itemgetters); serde must walk containers when
    packing closures — a regression here breaks every lowered Project."""
    from repro.core import serde

    fns = [lambda r: r + 1, lambda r: r * 2]

    def apply_all(r):
        return tuple(f(r) for f in fns)

    g = serde.loads_fn(serde.dumps_fn(apply_all))
    assert g(3) == (4, 6)

    def make(fs):
        def run(r):
            return [f(r) for f in fs]
        return run

    h = serde.loads_fn(serde.dumps_fn(make([lambda x: x - 1,
                                            lambda x: (x, x)])))
    assert h(5) == [4, (5, 5)]

    table = {"a": lambda x: x + 10, "b": len}

    def via_dict(r):
        return table["a"](r)

    k = serde.loads_fn(serde.dumps_fn(via_dict))
    assert k(1) == 11


def test_read_csv_end_to_end_with_bool_parsing():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    csv = "a,1,true,1.5\nb,2,false,2.5\na,3,TRUE,3.5\nc,4,0,4.5\n"
    ctx.upload("t.csv", csv.encode())
    df = ctx.read_csv("t.csv", [("s", "str"), ("n", "int"),
                                ("flag", "bool"), ("x", "float")], 2)
    rows = sorted(df.collect())
    assert rows == [("a", 1, True, 1.5), ("a", 3, True, 3.5),
                    ("b", 2, False, 2.5), ("c", 4, False, 4.5)]
    q = (df.where(col("flag"))
         .groupBy("s").agg(sum_(col("n")).alias("t"),
                           max_(col("x")).alias("hi")))
    assert sorted(q.collect()) == [("a", 4, 3.5)]
    assert repr(df) == "DataFrame[s:str, n:int, flag:bool, x:float]"
    assert df.columns == ("s", "n", "flag", "x")
    assert_no_leaks(ctx)


def test_expression_operators_and_errors():
    import pytest
    from repro.sql import Schema, udf
    from repro.sql.expr import AggExpr, Lit, dtype_serde_char

    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(4, 2.0, "ab", True),
                           (9, 3.0, "cd", False)], 2)
          .toDF([("i", "int"), ("f", "float"), ("s", "str"),
                 ("b", "bool")]))
    q = df.select(
        (col("i") - lit(1)).alias("sub"),
        (col("i") / lit(2)).alias("div"),
        (col("i") % lit(3)).alias("mod"),
        (col("i") <= lit(4)).alias("le"),
        (col("i") >= lit(9)).alias("ge"),
        (col("b") | (col("i") != lit(4))).alias("orr"),
        (~col("b")).alias("inv"),
        (col("s") + lit("!")).alias("cat"),
        col("f").cast("str").alias("fs"),
        col("s").substr(1, 1).alias("s1"),
        col("i").cast("bool").alias("ib"),
    )
    assert sorted(q.collect()) == sorted([
        (3, 2.0, 1, True, False, True, False, "ab!", "2.0", "a", True),
        (8, 4.5, 0, False, True, True, True, "cd!", "3.0", "c", True),
    ])
    # dtype checking
    sch = df.schema
    with pytest.raises(TypeError, match="arithmetic"):
        (col("s") - lit(1)).dtype(sch)
    with pytest.raises(TypeError, match="division"):
        (col("s") / lit(1)).dtype(sch)
    with pytest.raises(TypeError, match="boolean"):
        (col("i") & col("b")).dtype(sch)
    with pytest.raises(TypeError, match="boolean"):
        (~col("i")).dtype(sch)
    with pytest.raises(TypeError, match="substr"):
        col("i").substr(1, 2).dtype(sch)
    with pytest.raises(TypeError, match="avg"):
        from repro.sql import avg_
        avg_(col("s")).dtype(sch)
    with pytest.raises(TypeError, match="sum"):
        sum_(col("s")).dtype(sch)
    with pytest.raises(TypeError, match="unsupported literal"):
        Lit(object())
    with pytest.raises(ValueError, match="unknown operator"):
        from repro.sql.expr import BinOp
        BinOp("**", col("i"), lit(2))
    with pytest.raises(ValueError, match="unknown aggregate"):
        AggExpr("median", col("i"))
    with pytest.raises(ValueError, match="argument"):
        AggExpr("sum")
    with pytest.raises(ValueError, match="cannot cast"):
        col("i").cast("complex")
    with pytest.raises(ValueError, match="unknown dtype"):
        Schema([("x", "decimal")])
    with pytest.raises(ValueError, match="duplicate"):
        Schema([("x", "int"), ("x", "int")])
    # schema helpers
    assert len(sch) == 4 and sch == df.schema and hash(sch) == hash(sch)
    assert "i:int" in repr(sch)
    assert dtype_serde_char("list:list:str") == "l(l(s))"
    # udf evaluation + explain tag
    double = udf(lambda x: x * 2, "int", name="double")
    got = sorted(df.select(double(col("i")).alias("d")).collect())
    assert got == [(8,), (18,)]
    assert repr(col("i") + lit(1)) == "<expr (i + 1)>"
    assert repr(sum_(col("i")).alias("t")) == "<agg t:=sum(i)>"


def test_dataframe_take_and_misc_guards():
    import pytest
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i, i) for i in range(10)], 2)
          .toDF([("a", "int"), ("b", "int")]))
    assert df.take(3) == [(0, 0), (1, 1), (2, 2)]
    with pytest.raises(ValueError, match="n >= 0"):
        df.limit(-1)
    # groupBy() with no keys is a global aggregate: one row
    assert df.groupBy().agg(count_().alias("n")).collect() == [(10,)]
    with pytest.raises(ValueError, match="at least one key"):
        df.orderBy()
    with pytest.raises(ValueError, match="at least one aggregate"):
        df.groupBy("a").agg()
    with pytest.raises(TypeError, match="bad select argument"):
        df.select(42)
    with pytest.raises(TypeError, match="bad orderBy key"):
        df.orderBy(42)
    # orderBy accepts aliases and expressions; mixed directions
    out = df.orderBy((col("a") % lit(3)).alias("m"), "a",
                     ascending=[True, False]).collect()
    assert out[0] == (9, 9)  # m=0 group, then a desc


def test_declared_schema_never_coerces_mismatched_types():
    """Review regression: struct.pack would silently coerce int->float64
    (and bool->int64) under a declared schema; conformance checking must
    force the fallback so columnar on/off return IDENTICAL values."""
    rows = [(1, 2), (1, 5), (2, 3)]  # ints in a column declared float
    outs = {}
    for columnar in (True, False):
        ctx = FlintContext("flint",
                           FlintConfig(concurrency=4,
                                       columnar_batches=columnar))
        df = ctx.parallelize(rows, 2).toDF([("k", "int"), ("v", "float")])
        outs[columnar] = sorted(
            df.groupBy("k").agg(min_(col("v")).alias("lo")).collect())
    assert outs[True] == outs[False]
    assert all(type(lo) is int for _, lo in outs[True])  # NOT 2.0


def test_repeated_withcolumn_chains_do_not_explode_the_plan():
    """Review regression: Project-merge used to inline a twice-referenced
    non-trivial column at every level -> 2^n expression growth."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = ctx.parallelize([(1,)], 1).toDF([("x0", "int")])
    for i in range(18):
        df = df.withColumn(f"x{i + 1}",
                           col(f"x{i}") + col(f"x{i}"))
    plan = df.explain()
    assert len(plan) < 20_000, f"plan blew up to {len(plan)} chars"
    assert df.select("x18").collect() == [(2 ** 18,)]


def test_cached_frame_shares_one_materialization_across_derived_queries():
    """Review regression: derived queries each cached their OWN lineage;
    now the cache point is a plan barrier and both hits replan from one
    materialization."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    rows = [(i % 4, i) for i in range(40)]
    base_rdd = ctx.parallelize(rows, 2)
    base = (base_rdd.toDF([("k", "int"), ("v", "int")])
            .groupBy("k").agg(sum_(col("v")).alias("t"))
            .cache())
    assert "Cached[]" in base.explain()
    first = sorted(base.where(col("t") > lit(0)).collect())
    tokens_after_first = set(k.split("/")[1]
                             for k in ctx.store.list("_cache/"))
    assert len(tokens_after_first) == 1  # exactly one materialization
    invokes = ctx.ledger.lambda_requests
    second = sorted(base.where(col("t") > lit(10**9)).collect())
    assert second == [] and len(first) == 4
    # the second derived query replanned from the cache: no aggregation
    # shuffle re-ran, and no NEW cache token appeared
    assert set(k.split("/")[1] for k in ctx.store.list("_cache/")) \
        == tokens_after_first
    assert ctx.ledger.lambda_requests - invokes < invokes
    # and the user's RDD object was never mutated by df.cache()
    assert base_rdd.cached is False
    ctx.clear_cache()


def test_merged_filters_short_circuit():
    """Review regression: the optimizer merges sequential wheres into one
    AND; the later guard must not evaluate on rows the earlier filter
    excludes (eager operator.and_ raised ZeroDivisionError here)."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(0, 1), (2, 1), (4, 1)], 2)
          .toDF([("n", "int"), ("one", "int")]))
    q = (df.where(col("n") != lit(0))
           .where(lit(100.0) / col("n").cast("float") > lit(0.0)))
    assert sorted(q.collect()) == [(2, 1), (4, 1)]
    assert sorted(q.collect(optimize=False)) == [(2, 1), (4, 1)]


def test_withcolumn_replacement_preserves_position():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([("a", 1, "x")], 1)
          .toDF([("name", "str"), ("n", "int"), ("tag", "str")]))
    out = df.withColumn("n", col("n") * lit(10))
    assert out.columns == ("name", "n", "tag")
    assert out.collect() == [("a", 10, "x")]


def test_comparison_dtype_mismatch_fails_at_plan_time():
    import pytest
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = ctx.parallelize([(1,)], 1).toDF([("n", "int")])
    with pytest.raises(TypeError, match="cannot compare"):
        df.where(col("n") < lit("5"))


def test_count_on_sorted_limited_plan_skips_the_driver_sort():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(i,) for i in range(30)], 3)
          .toDF([("a", "int")]))
    assert df.orderBy("a").count() == 30
    assert df.orderBy("a", ascending=False).limit(7).count() == 7
    assert df.limit(40).count() == 30


_NT = __import__("collections").namedtuple("_NT", "tag n")


def test_round3_review_regressions():
    """orderBy validates its ascending list; substr rejects 0-based
    starts; serde keeps namedtuple closures intact (exact list/tuple
    only in the container walk)."""
    import pytest
    from repro.core import serde

    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    df = (ctx.parallelize([(1, 2)], 1)
          .toDF([("k", "int"), ("v", "int")]))
    with pytest.raises(ValueError, match="ascending"):
        df.orderBy("k", "v", ascending=[True])
    with pytest.raises(ValueError, match="1-based"):
        col("s").substr(0, 2)
    with pytest.raises(ValueError, match="1-based"):
        col("s").substr(1, -1)

    cfg = _NT("x", 3)

    def use_nt(r):
        return (cfg.tag, cfg.n + r)

    fn = serde.loads_fn(serde.dumps_fn(use_nt))
    assert fn(1) == ("x", 4)
    assert type(fn.__closure__[0].cell_contents) is _NT


_CYCLIC = []
_CYCLIC.append(_CYCLIC)


def test_serde_cyclic_container_global_falls_back_to_pickle():
    """Review regression: the container walk must not recurse forever on
    a cyclic global — cycles take the pickle path like before."""
    from repro.core import serde

    def f():
        return len(_CYCLIC)

    fn = serde.loads_fn(serde.dumps_fn(f))
    assert fn() == 1


# ------------------------------------------------- SQL NULL semantics
# (three-valued logic, docs/dataframe.md): NULLs enter rows via
# outer-join padding; every operator propagates them, where() drops
# NULL-valued predicates, and the vectorized path must agree with the
# row path exactly (falling back to row closures where needed).

_NULL_ROWS = [(1, 10), (2, None), (3, 30), (4, None), (5, 50)]
_NULL_SCHEMA = [("k", "int"), ("v", "int")]


def _null_df(vectorize):
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            vectorize=vectorize))
    return ctx, ctx.parallelize(_NULL_ROWS, 2).toDF(_NULL_SCHEMA)


@pytest.mark.parametrize("vectorize", [True, False],
                         ids=["vectorized", "rows"])
def test_null_predicates_use_three_valued_logic(vectorize):
    ctx, df = _null_df(vectorize)
    # NULL > 15 is NULL, not False: where() drops it, and so does the
    # NEGATED predicate (NOT NULL is NULL)
    assert sorted(df.where(col("v") > lit(15)).collect()) == \
        [(3, 30), (5, 50)]
    assert sorted(df.where(~(col("v") > lit(15))).collect()) == [(1, 10)]
    # OR: NULL | True is True (row k=2 survives via its other leg)
    got = df.where((col("v") > lit(15)) | (col("k") == lit(2))).collect()
    assert sorted(got) == [(2, None), (3, 30), (5, 50)]
    # AND: True & NULL is NULL (dropped), False & NULL is False
    got = df.where((col("k") > lit(0)) & (col("v") > lit(15))).collect()
    assert sorted(got) == [(3, 30), (5, 50)]
    assert sorted(df.where((col("k") < lit(0)) & (col("v") > lit(15)))
                  .collect()) == []
    assert_no_leaks(ctx)


@pytest.mark.parametrize("vectorize", [True, False],
                         ids=["vectorized", "rows"])
def test_null_propagates_through_operators(vectorize):
    ctx, df = _null_df(vectorize)
    got = sorted(df.select("k", (col("v") + lit(1)).alias("v1"),
                           col("v").cast("float").alias("vf")).collect())
    assert got == [(1, 11, 10.0), (2, None, None), (3, 31, 30.0),
                   (4, None, None), (5, 51, 50.0)]
    ctx2 = FlintContext("flint", FlintConfig(concurrency=4,
                                             vectorize=vectorize))
    sdf = ctx2.parallelize([("alpha",), (None,), ("beta",)], 2) \
        .toDF([("s", "str")])
    got = sorted(sdf.select(col("s").substr(1, 2).alias("p")).collect(),
                 key=lambda r: (r[0] is None, r))
    assert got == [("al",), ("be",), (None,)]
    # str equality against NULL is NULL -> dropped (the vectorized str
    # kernel falls back to row closures for this batch)
    assert sorted(sdf.where(col("s") == lit("alpha")).collect()) == \
        [("alpha",)]
    assert_no_leaks(ctx)
    assert_no_leaks(ctx2)


def test_null_semantics_row_vector_parity():
    """The SAME queries through the fused vectorized lowering and the
    row-closure lowering return identical rows — None never silently
    coerces in either path."""
    for q in (lambda df: df.where(col("v") >= lit(10)),
              lambda df: df.select("k", (col("v") * lit(3)).alias("t")),
              lambda df: df.where((col("v") > lit(10)) |
                                  (col("k") > lit(3))),
              lambda df: df.groupBy((col("k") % lit(2)).alias("g")).agg(
                  count_().alias("n"))):
        _, dv = _null_df(True)
        _, dr = _null_df(False)
        assert sorted(q(dv).collect(), key=repr) == \
            sorted(q(dr).collect(), key=repr)


def test_outer_join_padding_flows_through_null_semantics():
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    left = ctx.parallelize([(1, "a"), (2, "b"), (3, "c")], 2) \
        .toDF([("k", "int"), ("s", "str")])
    right = ctx.parallelize([(1, 100), (3, 300)], 2) \
        .toDF([("k", "int"), ("w", "int")])
    j = left.join(right, on="k", how="left")
    assert sorted(j.collect()) == [(1, "a", 100), (2, "b", None),
                                   (3, "c", 300)]
    # padded NULL drops out of comparisons and propagates through math
    assert sorted(j.where(col("w") >= lit(0)).collect()) == \
        [(1, "a", 100), (3, "c", 300)]
    got = j.withColumn("w2", col("w") + lit(1)) \
        .where(col("w2") > lit(101)).collect()
    assert sorted(got) == [(3, "c", 300, 301)]
    assert_no_leaks(ctx)
