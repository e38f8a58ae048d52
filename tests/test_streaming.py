"""Streaming engine suite (docs/streaming.md): windowed streaming
queries over replayed event streams must produce finalized results
IDENTICAL to the equivalent batch query — including after a mid-stream
driver kill/resume from checkpoint and under seeded chaos — on both
shuffle transports, with zero leaked queues or objects. Plus unit
coverage for the window/watermark state machine, the source contract,
late-data accounting, the per-window transport cost model, and the
service integration (long-running admission + between-batch quota)."""

import os
from decimal import ROUND_HALF_UP, Context, Decimal

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import FaultPlan, FlintConfig, FlintContext
from repro.core.scheduler import StageFailure
from repro.sql.dataframe import DataFrame
from repro.sql.expr import (Schema, avg_, col, collect_list, count_, lit,
                            sum_)
from repro.sql.plan import Window
from repro.streaming import (PANE_COL, EventGenerator, S3PrefixTailer,
                             WindowSpec, WindowState, read_stream)
from repro.svc import FlintService

CHAOS_SEED = int(os.environ.get("FLINT_CHAOS_SEED", "0"))

#: every transient prefix that must be empty after queries clean up —
#: streaming checkpoints included once the query's cleanup() ran
TRANSIENT_PREFIXES = ("_exchange/", "_spill/", "_payload/", "_result/",
                      "_broadcast/", "_stream/")

BACKENDS = ["sqs", "s3"]


def _cfg(backend="sqs", **kw):
    kw.setdefault("concurrency", 4)
    kw.setdefault("visibility_timeout_s", 0.5)
    kw.setdefault("drain_timeout_s", 1.5)
    return FlintConfig(shuffle_backend=backend, **kw)


def assert_no_leaks(ctx):
    leaked = [k for p in TRANSIENT_PREFIXES for k in ctx.store.list(p)]
    assert not leaked, f"leaked transient objects: {leaked[:5]}"
    sched = ctx.last_scheduler
    if sched is not None:
        assert sched.sqs._queues == {}, "leaked queues"


def py_reference(events, size, slide=None, pred=None):
    """Driver-independent reference: sum/count per (window, key),
    computed row-at-a-time in plain Python."""
    slide = size if slide is None else slide
    out = {}
    for ts, key, val in events:
        if pred is not None and not pred(ts, key, val):
            continue
        pane = ts - ts % slide
        for ws in range(pane - size + slide, pane + 1, slide):
            cur = out.setdefault((ws, key), [0, 0])
            cur[0] += val
            cur[1] += 1
    return sorted((ws, ws + size, k, t, n)
                  for (ws, k), (t, n) in out.items())


def _sum_count_stream(ctx, src, size, slide=None, **start_kw):
    start_kw.setdefault("allowed_lateness", src.max_delay)
    return (read_stream(ctx, src)
            .window("ts", size, slide)
            .groupBy("key")
            .agg(sum_(col("val")).alias("total"), count_().alias("n"))
            .start(start_kw.pop("name", "q"), **start_kw))


# ------------------------------------------------ window state machine


def test_window_spec_validation():
    with pytest.raises(ValueError):
        WindowSpec("ts", 0)
    with pytest.raises(ValueError):
        WindowSpec("ts", 10, -2)
    with pytest.raises(ValueError):
        WindowSpec("ts", 10, 3)  # size not a multiple of slide
    assert list(WindowSpec("ts", 30, 10).windows_of(60)) == [40, 50, 60]
    assert list(WindowSpec("ts", 10).windows_of(20)) == [20]


def _tumbling_state(size=10, lateness=0):
    import operator
    return WindowState(WindowSpec("ts", size), [operator.add],
                       lambda slots: [slots[0]], lateness)


def test_window_state_watermark_closes_left_to_right():
    st_ = _tumbling_state()
    st_.merge(0, ("a",), [5], 1)
    st_.merge(10, ("a",), [7], 2)
    st_.merge(10, ("b",), [1], 1)
    assert st_.advance(9.0) == []           # window [0,10) not yet past
    assert st_.advance(10.0) == [(0, 10, "a", 5)]
    assert st_.frontier == 10
    # later watermarks close later windows, keys in sorted order
    assert st_.advance(25.0) == [(10, 20, "a", 7), (10, 20, "b", 1)]
    # watermarks never regress
    st_.advance(3.0)
    assert st_.watermark == 25.0


def test_window_state_sliding_recombines_panes():
    import operator
    st_ = WindowState(WindowSpec("ts", 20, 10), [operator.add],
                      lambda s: [s[0]])
    st_.merge(0, ("k",), [1], 1)
    st_.merge(10, ("k",), [2], 1)
    st_.merge(20, ("k",), [4], 1)
    out = st_.advance(float("inf"))
    # windows [-10,10) [0,20) [10,30) [20,40): pane sums recombine
    assert out == [(-10, 10, "k", 1), (0, 20, "k", 3),
                   (10, 30, "k", 6), (20, 40, "k", 4)]


def test_window_state_allowed_lateness_updates_then_drops():
    st_ = _tumbling_state(lateness=5)
    st_.merge(0, ("a",), [1], 1)
    assert st_.advance(12.0) == []          # held open for late updates
    assert st_.merge(0, ("a",), [9], 1)     # late UPDATE lands
    assert st_.advance(15.0) == [(0, 10, "a", 10)]
    assert not st_.merge(0, ("a",), [3], 2)  # after close: drop + count
    assert st_.late_dropped == 2


def test_window_state_snapshot_restore_roundtrip():
    st_ = _tumbling_state(lateness=2)
    st_.merge(0, ("a",), [1], 1)
    st_.merge(10, ("b",), [2], 1)
    st_.advance(13.0)
    snap = st_.snapshot()
    st2 = _tumbling_state(lateness=2)
    st2.restore(snap)
    assert st2.advance(None) == st_.advance(None)
    assert st2.advance(float("inf")) == st_.advance(float("inf"))


@settings(max_examples=25, deadline=None)
@given(size_panes=st.integers(1, 3), seed=st.integers(0, 10 ** 6),
       lateness=st.sampled_from([0, 5, 100]))
def test_window_state_property_vs_bruteforce(size_panes, seed, lateness):
    """Any in-order watermark schedule with lateness covering the
    disorder emits exactly the brute-force window sums."""
    import operator
    import random
    rng = random.Random(seed)
    slide = 10
    size = slide * size_panes
    events = [(rng.randrange(60), rng.choice("ab"), rng.randrange(100))
              for _ in range(rng.randrange(1, 60))]
    st_ = WindowState(WindowSpec("ts", size, slide), [operator.add],
                      lambda s: [s[0]], lateness)
    out = []
    for i in range(0, len(events), 7):
        chunk = events[i:i + 7]
        for ts, k, v in chunk:
            pane = ts - ts % slide
            st_.merge(pane, (k,), [v], 1)
        out.extend(st_.advance(max(ts for ts, _, _ in chunk)))
    out.extend(st_.advance(float("inf")))
    if lateness >= 60:  # nothing can drop: exact equality
        assert sorted(out) == [(ws, we, k, t) for ws, we, k, t, _n in
                               py_reference(events, size, slide)]
        assert st_.late_dropped == 0
    # whatever closed is final: no window may appear twice
    assert len({(r[0], r[2]) for r in out}) == len(out)


# ------------------------------------------------ Window plan node


def test_window_plan_node_in_batch_dataframe():
    ctx = FlintContext("flint", _cfg())
    rows = [(3, "a", 1), (17, "b", 2), (25, "a", 3)]
    df = (DataFrame.from_rdd(ctx.parallelize(rows, 2),
                             EventGenerator.schema)
          .withWindow("ts", 10))
    assert df.schema.names[-1] == "window_start"
    got = sorted(df.collect())
    assert got == [(3, "a", 1, 0), (17, "b", 2, 10), (25, "a", 3, 20)]
    assert "Window[" in df.explain()
    # the optimizer pushes filters BELOW the pane projection but must
    # keep the Window node itself intact (explain still shows it)
    assert "Window[" in df.where(col("key") == lit("a")).explain()
    with pytest.raises(ValueError):
        df.withWindow("ts", 10, 3)  # size % slide != 0
    with pytest.raises(TypeError):
        DataFrame.from_rdd(ctx.parallelize(rows, 2),
                           Schema([("ts", "str"), ("key", "str"),
                                   ("val", "int")])).withWindow("ts", 10)
    assert_no_leaks(ctx)


def test_window_node_survives_optimizer():
    from repro.sql.optimizer import optimize
    ctx = FlintContext("flint", _cfg())
    rows = [(i, "k", i) for i in range(20)]
    df = (DataFrame.from_rdd(ctx.parallelize(rows, 2),
                             EventGenerator.schema)
          .withWindow("ts", 10)
          .where(col("val") >= lit(5)))
    plan = optimize(df.plan, ctx)

    def find_window(node):
        if isinstance(node, Window):
            return node
        for c in node.children():
            w = find_window(c)
            if w is not None:
                return w
        return None
    assert find_window(plan) is not None
    assert sorted(df.collect()) == [(i, "k", i, i - i % 10)
                                    for i in range(5, 20)]


# ------------------------------------------------ stream == batch


@pytest.mark.parametrize("backend", BACKENDS)
def test_tumbling_stream_matches_batch(backend):
    ctx = FlintContext("flint", _cfg(backend))
    src = EventGenerator(seed=11, total=400, rate=10, late_prob=0.3,
                         max_delay=4)
    q = _sum_count_stream(ctx, src, size=10, transport=backend,
                          batch_size=130)
    got = q.run()
    assert got == py_reference(src.read(0, 400), 10)
    assert q.late_dropped == 0
    assert q.stats()["transports"] == [backend] * 4
    q.cleanup()
    assert_no_leaks(ctx)


def test_sliding_stream_matches_batch():
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=5, total=300, rate=10, max_delay=3)
    q = _sum_count_stream(ctx, src, size=30, slide=10, batch_size=90,
                          name="slide")
    assert q.run() == py_reference(src.read(0, 300), 30, 10)
    q.cleanup()
    assert_no_leaks(ctx)


def test_transform_ops_and_avg_match_batch():
    """where/withColumn/select compose ahead of the window; avg
    decomposes into sum+count slots that merge across batches."""
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=2, total=300, rate=10, max_delay=2)
    q = (read_stream(ctx, src)
         .where(col("val") >= lit(100))
         .withColumn("v2", col("val") * lit(2))
         .select("ts", "key", col("v2").alias("val"))
         .window("ts", 20)
         .groupBy("key")
         .agg(sum_(col("val")).alias("t"), count_().alias("n"),
              avg_(col("val")).alias("m"))
         .start("ops", allowed_lateness=2, batch_size=100))
    got = q.run()
    ref = py_reference(src.read(0, 300), 20,
                       pred=lambda ts, k, v: v >= 100)
    assert got == [(ws, we, k, 2 * t, n, 2 * t / n)
                   for ws, we, k, t, n in ref]
    q.cleanup()
    assert_no_leaks(ctx)


def test_decimal_avg_matches_batch_rounding():
    """A decimal avg merges as sum + count across batches and finishes
    as the batch engine's: scale s + 4, rounded half up."""
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=3, total=200, rate=10, max_delay=2)
    q = (read_stream(ctx, src)
         .withColumn("m", col("val").cast("decimal(9,2)")
                     * lit(Decimal("0.01")))
         .window("ts", 20)
         .groupBy("key")
         .agg(sum_(col("m")).alias("t"), avg_(col("m")).alias("a"))
         .start("dec", allowed_lateness=2, batch_size=70))
    got = q.run()
    exact = Context(prec=60)
    ref = py_reference(src.read(0, 200), 20)
    # m has scale 2 + 2, so its avg has scale 8
    want = [(ws, we, k, Decimal(t) / 100,
             exact.divide(Decimal(t) / 100, n).quantize(
                 Decimal("1E-8"), rounding=ROUND_HALF_UP))
            for ws, we, k, t, n in ref]
    assert got == want
    assert all(type(r[4]) is Decimal for r in got)
    q.cleanup()
    assert_no_leaks(ctx)


def test_stream_static_join():
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=9, total=200, rate=10, n_keys=3,
                         max_delay=2)
    dims = DataFrame.from_rdd(
        ctx.parallelize([("k0", 10), ("k1", 100), ("k2", 1000)], 2),
        Schema([("key", "str"), ("mult", "int")]))
    q = (read_stream(ctx, src)
         .join(dims, on="key")
         .withColumn("val", col("val") * col("mult"))
         .window("ts", 20)
         .groupBy("key")
         .agg(sum_(col("val")).alias("t"), count_().alias("n"))
         .start("join", allowed_lateness=2, batch_size=80))
    mult = {"k0": 10, "k1": 100, "k2": 1000}
    ref = py_reference([(ts, k, v * mult[k])
                        for ts, k, v in src.read(0, 200)], 20)
    assert q.run() == ref
    q.cleanup()
    assert_no_leaks(ctx)


def test_stream_static_join_rejects_static_preserving_shapes():
    ctx = FlintContext("flint", _cfg())
    src = EventGenerator(seed=1, total=10)
    dims = DataFrame.from_rdd(ctx.parallelize([("k0", 1)], 1),
                              Schema([("key", "str"), ("mult", "int")]))
    for how in ("right", "outer"):
        with pytest.raises(ValueError, match="stream-static"):
            read_stream(ctx, src).join(dims, on="key", how=how)


def test_late_data_dropped_and_counted():
    """With zero allowed lateness a bursty out-of-order stream drops
    SOME contributions (counted), and every emitted window is final."""
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=4, total=300, rate=10, late_prob=0.6,
                         max_delay=8)
    q = _sum_count_stream(ctx, src, size=10, allowed_lateness=0,
                          batch_size=60, name="late")
    got = q.run()
    assert q.late_dropped > 0
    assert len({(r[0], r[2]) for r in got}) == len(got)  # finalized once
    # drops only ever SHRINK a window's sum/count vs the reference
    ref = {(r[0], r[2]): r[3:] for r in py_reference(src.read(0, 300), 10)}
    for ws, _we, k, t, n in got:
        rt, rn = ref[(ws, k)]
        assert t <= rt and n <= rn
    q.cleanup()
    assert_no_leaks(ctx)


def test_collect_list_and_misuse_rejected():
    ctx = FlintContext("flint", _cfg())
    src = EventGenerator(seed=0, total=10)
    ws = read_stream(ctx, src).window("ts", 10).groupBy("key")
    with pytest.raises(ValueError, match="collect_list"):
        ws.agg(collect_list(col("val")).alias("vs"))
    with pytest.raises(ValueError, match="at least one aggregate"):
        ws.agg()
    with pytest.raises(TypeError):
        ws.agg(col("val"))
    with pytest.raises(ValueError, match="reserved"):
        (read_stream(ctx, src).withColumn(PANE_COL, lit(1))
         .window("ts", 10))
    with pytest.raises(ValueError, match="batch_size"):
        _sum_count_stream(ctx, src, size=10, batch_size=0)
    with pytest.raises(ValueError, match="for_each_batch"):
        read_stream(ctx, src).for_each_batch(lambda b, r: None)


# ------------------------------------------------ exactly-once recovery


def _resumable(ctx, name, **kw):
    src = EventGenerator(seed=3, total=400, rate=10, late_prob=0.2,
                         max_delay=3)
    return _sum_count_stream(ctx, src, size=10, batch_size=120,
                             name=name, **kw)


@pytest.mark.parametrize("backend", BACKENDS)
def test_crash_resume_is_exactly_once(backend):
    ctx = FlintContext("flint", _cfg(backend))
    expected = _resumable(ctx, "ref").run()
    q1 = _resumable(ctx, "crash")
    q1.step()
    q1.step()
    # driver dies here; a fresh driver under the same name resumes from
    # the checkpoint: already-consumed offsets are NOT re-read, emitted
    # windows are NOT re-finalized
    q2 = _resumable(ctx, "crash")
    assert q2.batch == 2 and q2.offset == 240
    assert q2.run() == expected
    q1.cleanup()
    q2.cleanup()
    _resumable(ctx, "ref").cleanup()
    assert_no_leaks(ctx)


def test_lost_latest_checkpoint_falls_back_to_previous():
    """An acknowledged-but-lost checkpoint write must not lose data: the
    resumed driver falls back to the prior checkpoint and the replayable
    source re-reads the lost batch."""
    ctx = FlintContext("flint", _cfg("sqs"))
    expected = _resumable(ctx, "ref2").run()
    q1 = _resumable(ctx, "lost")
    for _ in range(3):
        q1.step()
    assert ctx.store.list("_stream/lost/ckpt/") == [
        "_stream/lost/ckpt/00000002", "_stream/lost/ckpt/00000003"]
    ctx.store.delete("_stream/lost/ckpt/00000003")
    q2 = _resumable(ctx, "lost")
    assert q2.batch == 2  # fell back one batch
    assert q2.run() == expected
    q1.cleanup()
    q2.cleanup()
    _resumable(ctx, "ref2").cleanup()
    assert_no_leaks(ctx)


def test_checkpoint_retention_and_cleanup():
    ctx = FlintContext("flint", _cfg("sqs"))
    q = _resumable(ctx, "ret")
    q.run()
    ckpts = ctx.store.list("_stream/ret/ckpt/")
    assert len(ckpts) == 2  # last two retained, older ones deleted
    assert q.cleanup() == 2
    assert ctx.store.list("_stream/") == []
    with pytest.raises(RuntimeError, match="stopped"):
        q.step()
    assert_no_leaks(ctx)


def test_checkpointing_disabled_runs_fresh():
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=6, total=100, rate=10, max_delay=2)
    q = _sum_count_stream(ctx, src, size=10, batch_size=50,
                          checkpoint=False, name="nock")
    assert q.run() == py_reference(src.read(0, 100), 10)
    assert ctx.store.list("_stream/") == []
    q.cleanup()
    assert_no_leaks(ctx)


def test_sink_prefix_is_idempotent_across_resume():
    ctx = FlintContext("flint", _cfg("sqs"))
    q1 = _resumable(ctx, "sink", sink_prefix="out/sink")
    q1.step()
    q1.step()
    q2 = _resumable(ctx, "sink", sink_prefix="out/sink")
    expected = q2.run()
    per_window = {}
    for key in ctx.store.list("out/sink/"):
        for row in ctx.store.get_obj(key):
            per_window.setdefault(key, []).append(row)
    flat = sorted(r for rows in per_window.values() for r in rows)
    assert flat == sorted(expected)  # replay overwrote, never duplicated
    q1.cleanup()
    q2.cleanup()
    ctx.store.delete_prefix("out/")
    assert_no_leaks(ctx)


def test_for_each_batch_sees_every_finalized_row():
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=8, total=200, rate=10, max_delay=2)
    seen = []
    q = _sum_count_stream(ctx, src, size=10, batch_size=60, name="feb",
                          for_each_batch=lambda b, rows:
                          seen.append((b, rows)))
    got = q.run()
    assert [r for _, rows in seen for r in rows] == got
    assert [b for b, _ in seen] == sorted({b for b, _ in seen})
    q.cleanup()
    assert_no_leaks(ctx)


# ------------------------------------------------ transport cost model


def test_transport_choice_follows_observed_volume():
    """Quiet windows ride SQS; a multi-MB burst flips the EWMA to S3 and
    sustained quiet flips it back — per-batch, from the one cost model
    (core.costs.pick_shuffle_transport)."""
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=0, total=100)
    q = _sum_count_stream(ctx, src, size=10, name="vol")
    assert q._choose_transport(100) == "sqs"
    assert q._choose_transport(2_000_000) == "s3"
    while q._choose_transport(100) == "s3":
        pass  # EWMA decays back
    assert q.transports[0] == "sqs" and "s3" in q.transports \
        and q.transports[-1] == "sqs"
    q.stop()
    ctx.store.delete_prefix("_stream/")


def test_pinned_transport_never_consults_cost_model():
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=0, total=100)
    q = _sum_count_stream(ctx, src, size=10, transport="s3", name="pin")
    assert q._choose_transport(1) == "s3"
    assert q._volume is None
    q.stop()
    ctx.store.delete_prefix("_stream/")


# ------------------------------------------------ S3 prefix tailer


TAIL_SCHEMA = Schema([("ts", "int"), ("key", "str"), ("val", "int")])


def _csv(rows):
    return "\n".join(f"{t},{k},{v}" for t, k, v in rows).encode()


def test_s3_prefix_tailer_stream_matches_batch():
    ctx = FlintContext("flint", _cfg("sqs"))
    chunks = [[(i, f"k{i % 3}", i * 7) for i in range(c * 20, c * 20 + 20)]
              for c in range(4)]
    for c, rows in enumerate(chunks[:2]):
        ctx.store.put(f"events/{c:04d}.csv", _csv(rows))
    src = S3PrefixTailer(ctx.store, "events/", TAIL_SCHEMA)
    q = (read_stream(ctx, src)
         .window("ts", 10)
         .groupBy("key")
         .agg(sum_(col("val")).alias("t"), count_().alias("n"))
         .start("tail", batch_size=1))  # one object per batch
    q.step()
    # objects arriving AFTER the stream started join later batches
    for c, rows in enumerate(chunks[2:], start=2):
        ctx.store.put(f"events/{c:04d}.csv", _csv(rows))
    src.seal()
    got = q.run()
    assert got == py_reference([r for c in chunks for r in c], 10)
    assert q.batch >= 4  # at most one object consumed per batch
    q.cleanup()
    ctx.store.delete_prefix("events/")
    assert_no_leaks(ctx)


def test_tailer_offsets_replay_and_diverge():
    ctx = FlintContext("flint", _cfg())
    ctx.store.put("tl/a", _csv([(1, "k", 2)]))
    ctx.store.put("tl/b", _csv([(3, "k", 4)]))
    src = S3PrefixTailer(ctx.store, "tl/", TAIL_SCHEMA)
    assert src.initial() == ()
    o1 = src.next_offset((), 1)
    o2 = src.next_offset(o1, 5)
    assert o1 == ("tl/a",) and o2 == ("tl/a", "tl/b")
    assert src.read(o1, o2) == src.read(o1, o2) == [(3, "k", 4)]
    with pytest.raises(ValueError, match="diverged"):
        src.read(("tl/b",), o2)
    assert not src.exhausted(o2)
    src.seal()
    assert src.exhausted(o2) and not src.exhausted(o1)


# ------------------------------------------------ property: replayed
# stream == batch reference, any batch size, any window shape


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10 ** 6),
       batch_size=st.sampled_from([37, 90, 250]),
       panes=st.sampled_from([1, 2]))
def test_property_stream_equals_batch_reference(seed, batch_size, panes):
    ctx = FlintContext("flint", _cfg("sqs"))
    src = EventGenerator(seed=seed, total=220, rate=10, late_prob=0.25,
                         max_delay=4)
    q = _sum_count_stream(ctx, src, size=10 * panes, slide=10,
                          batch_size=batch_size, name=f"prop{seed}")
    got = q.run()
    assert got == py_reference(src.read(0, 220), 10 * panes, 10)
    q.cleanup()
    assert_no_leaks(ctx)


# ------------------------------------------------ service integration


def test_service_streaming_admission_and_quota():
    """A streaming query admits ONCE as a long-running job (batches do
    not re-queue at the gate), a second stream on the same session is
    refused, and a tenant crossing its budget is stopped BETWEEN batches
    with the structured quota failure. Neighbors are unaffected."""
    svc = FlintService(_cfg("sqs"), slot_capacity=4)
    svc.register_tenant("a")
    svc.register_tenant("broke", max_usd=1e-9)
    svc.register_tenant("rich")
    with svc.session("a") as s:
        src = EventGenerator(seed=1, total=200, rate=10, max_delay=2)
        q = (s.read_stream(src)
             .window("ts", 10)
             .groupBy("key")
             .agg(sum_(col("val")).alias("t"), count_().alias("n"))
             .start("svc-q", allowed_lateness=2, batch_size=60))
        with pytest.raises(RuntimeError, match="already runs"):
            s.read_stream(EventGenerator(seed=2, total=10)) \
                .window("ts", 10).groupBy("key") \
                .agg(count_().alias("n")).start("svc-q2")
        assert q.run() == py_reference(src.read(0, 200), 10)
        q.cleanup()
    with svc.session("broke") as s:
        src = EventGenerator(seed=1, total=200, rate=10, max_delay=2)
        q = (s.read_stream(src).window("ts", 10).groupBy("key")
             .agg(count_().alias("n"))
             .start("broke-q", batch_size=60))
        with pytest.raises(StageFailure) as ei:
            q.run()
        assert ei.value.error_type == "TenantQuotaExceeded"
        q.cleanup()
    with svc.session("rich") as s:  # neighbor unaffected, slot released
        src = EventGenerator(seed=1, total=60, rate=10, max_delay=2)
        q = (s.read_stream(src).window("ts", 10).groupBy("key")
             .agg(count_().alias("n")).start("rich-q", batch_size=60))
        assert q.run()
        q.cleanup()
    svc.close()
    assert all(v == 0 for v in svc.leak_report().values()), \
        svc.leak_report()


@pytest.mark.parametrize("backend", BACKENDS)
def test_service_streaming_under_chaos_with_lost_checkpoint(backend):
    """Seeded transient faults on every service call PLUS one eaten
    ``_stream/`` checkpoint write, with a driver kill/resume in the
    middle: the finalized windows still exactly match the fault-free
    batch reference, and nothing leaks."""
    plan = FaultPlan(seed=CHAOS_SEED * 31 + 7,
                     s3_error_prob=0.05, sqs_error_prob=0.05,
                     lose_keys=("chaos-q/ckpt/",))  # first ckpt write lost
    svc = FlintService(_cfg(backend, max_stage_retries=5,
                            retry_base_s=0.001, retry_cap_s=0.01),
                       fault_plan=plan, slot_capacity=4)
    svc.register_tenant("t")
    src = EventGenerator(seed=13, total=300, rate=10, late_prob=0.3,
                         max_delay=3)
    expected = py_reference(src.read(0, 300), 10)
    with svc.session("t") as s:

        def make_q():
            return (s.read_stream(EventGenerator(
                        seed=13, total=300, rate=10, late_prob=0.3,
                        max_delay=3))
                    .window("ts", 10)
                    .groupBy("key")
                    .agg(sum_(col("val")).alias("t"),
                         count_().alias("n"))
                    .start("chaos-q", allowed_lateness=3, batch_size=90))
        q1 = make_q()
        q1.step()
        q1.step()
        q1.stop()  # driver killed mid-stream (slot released)
        q2 = make_q()
        assert q2.run() == expected
        assert q2.cleanup() >= 1
        stray = [k for k in s.ctx.store.list("_collections/")]
        assert stray == [], f"staged batch data leaked: {stray[:5]}"
    svc.close()
    assert all(v == 0 for v in svc.leak_report().values()), \
        svc.leak_report()
