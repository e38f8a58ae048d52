"""SQS visibility-timeout semantics (receive claims, ack-after-fold,
redelivery) and the satellite bugfix regressions: serde kwdefaults,
serde self-reference, oversized-record spill, barrier-mode teardown."""

import operator
import pickle
import time

import pytest

from repro.core import (FlintConfig, FlintContext, FlintScheduler,
                        StageFailure, build_plan)
from repro.core.costs import CostLedger
from repro.core.queues import (Message, ObjectStoreSim, QueueGone, SQSSim,
                               SpillPointer, pack_records, unpack_records)
from repro.core import serde

TEXT = "\n".join(["the quick brown fox", "jumps over the lazy dog",
                  "the dog barks"] * 100).encode()

EXPECTED = {"the": 300, "quick": 100, "brown": 100, "fox": 100,
            "jumps": 100, "over": 100, "lazy": 100, "dog": 200, "barks": 100}


def wordcount(ctx, nparts=4, red_parts=3):
    ctx.upload("text.txt", TEXT)
    return dict(ctx.textFile("text.txt", nparts)
                .flatMap(lambda line: line.split())
                .map(lambda w: (w, 1))
                .reduceByKey(operator.add, red_parts)
                .collect())


# ------------------------------------------------ visibility unit tests


def _sim(vis=0.2, **kw):
    sqs = SQSSim(CostLedger(), visibility_timeout=vis, **kw)
    sqs.create_queue("q")
    return sqs


def test_receive_claims_instead_of_popping():
    sqs = _sim()
    sqs.send_batch("q", [Message(b"a", 0, "s0t0")])
    got = sqs.receive_many("q")
    assert len(got) == 1 and got[0].receipt is not None
    # in flight: invisible to a second receive, absent from the backlog
    assert sqs.receive_many("q") == []
    assert sqs.approx_len("q") == 0
    assert sqs.inflight_len("q") == 1


def test_unacked_message_redelivers_after_timeout():
    sqs = _sim(vis=0.15)
    sqs.send_batch("q", [Message(b"a", 0, "s0t0")])
    first_receipt = sqs.receive_many("q")[0].receipt
    time.sleep(0.2)
    again = sqs.receive_many("q")  # lazy sweep returns it to visible
    assert len(again) == 1 and (again[0].src, again[0].seq) == ("s0t0", 0)
    assert again[0].receipt != first_receipt  # fresh handle, fresh receive
    assert sqs.redeliveries == 1


def test_ack_deletes_and_duplicate_acks_are_idempotent():
    sqs = _sim(vis=0.15)
    sqs.send_batch("q", [Message(b"a", 0, "s0t0")])
    m = sqs.receive_many("q")[0]
    sqs.delete_batch("q", [m.receipt])
    sqs.delete_batch("q", [m.receipt])  # double ack: no-op
    time.sleep(0.2)
    assert sqs.receive_many("q") == []  # acked for good, never redelivered
    assert sqs.inflight_len("q") == 0


def test_stale_receipt_after_redelivery_is_a_noop():
    """An expired claim's old receipt must not delete the message out from
    under whoever re-received it."""
    sqs = _sim(vis=0.15)
    sqs.send_batch("q", [Message(b"a", 0, "s0t0")])
    old = sqs.receive_many("q")[0].receipt
    time.sleep(0.2)
    again = sqs.receive_many("q")  # redelivered under a new receipt
    assert len(again) == 1
    sqs.delete_batch("q", [old])  # stale: no-op
    assert sqs.inflight_len("q") == 1
    sqs.delete_batch("q", [again[0].receipt])
    assert sqs.inflight_len("q") == 0


def test_change_visibility_extends_the_claim():
    sqs = _sim(vis=0.15)
    sqs.send_batch("q", [Message(b"a", 0, "s0t0")])
    m = sqs.receive_many("q")[0]
    sqs.change_visibility("q", [m.receipt], 1.0)
    time.sleep(0.3)  # past the original deadline, inside the extension
    assert sqs.receive_many("q") == []
    assert sqs.inflight_len("q") == 1


def test_receive_from_deleted_queue_raises_queue_gone():
    sqs = _sim()
    sqs.delete_queue("q")
    with pytest.raises(QueueGone):
        sqs.receive_many("q")


def test_receive_many_drains_requested_backlog():
    """Adaptive drain sizing: one scheduler step can take the whole
    visible backlog, not a fixed 100."""
    sqs = _sim(vis=5.0)
    for i in range(0, 300, 10):
        sqs.send_batch("q", [Message(b"x", i + j, "s0t0")
                             for j in range(10)])
    backlog = sqs.approx_len("q")
    assert backlog == 300
    got = sqs.receive_many("q", min(1000, max(10, backlog)))
    assert len(got) == 300
    assert sqs.approx_len("q") == 0


def test_visibility_must_undercut_drain_timeout():
    """A visibility timeout at or above the drain timeout means a retried
    consumer gives up before its predecessor's claims expire — rejected
    up front instead of failing later with 'queue incomplete'."""
    with pytest.raises(ValueError, match="visibility_timeout_s"):
        FlintScheduler(FlintConfig(shuffle_backend="sqs",
                                   visibility_timeout_s=30.0,
                                   drain_timeout_s=30.0))
    FlintScheduler(FlintConfig(shuffle_backend="s3", visibility_timeout_s=30.0,
                               drain_timeout_s=30.0)).shutdown()  # s3: moot


# ------------------------------------- consumer failure is recoverable


@pytest.mark.parametrize("pipelined", [True, False])
def test_consumer_failure_recovers_with_identical_results(pipelined):
    """The acceptance criterion: a ShuffleRead task dying mid-task
    (fail_after_records) completes via retry with results identical to
    the fault-free run, in both modes, under duplicate_prob > 0."""
    cfg = dict(concurrency=4, flush_records=20, duplicate_prob=0.2,
               visibility_timeout_s=0.5, drain_timeout_s=8.0,
               pipeline_stages=pipelined)
    clean = wordcount(FlintContext("flint", FlintConfig(**cfg)))
    faulty = FlintContext("flint", FlintConfig(**cfg),
                          fault_plan={(1, 1): {"fail_after_records": 2}},
                          elastic_retries=0)
    assert wordcount(faulty) == clean == EXPECTED


@pytest.mark.parametrize("pipelined", [True, False])
def test_consumer_speculation_no_longer_splits_queue(pipelined):
    """A straggling consumer gets a speculative duplicate; the two drains
    race on acks (instead of destructively splitting the queue) and the
    loser aborts on QueueGone when the winner's queue is released."""
    ctx = FlintContext("flint",
                       FlintConfig(concurrency=8, pipeline_stages=pipelined,
                                   speculation_factor=2.0,
                                   speculation_min_done=2,
                                   visibility_timeout_s=0.5,
                                   # adaptive coalescing would fold these
                                   # deliberately tiny reduce partitions
                                   # into one task — this test needs the
                                   # full 6 to race a speculative twin
                                   coalesce_min_bytes=0),
                       fault_plan={(1, 0): {"straggle_s": 0.8}})
    assert wordcount(ctx, nparts=4, red_parts=6) == EXPECTED
    reduce_stats = ctx.last_scheduler.stage_stats[-1]
    assert reduce_stats["speculated"] >= 1


@pytest.mark.parametrize("pipelined", [True, False])
def test_mid_pipeline_consumer_writer_retry_is_deterministic(pipelined):
    """A shuffle-reading task that WRITES another shuffle re-emits
    byte-identical (src, seq) messages on retry (output is sorted before
    partitioning/packing), so downstream dedup never mixes two attempts'
    packings — even when the first attempt flushed partial output before
    dying."""
    def three_stage(ctx):
        ctx.upload("text.txt", TEXT)
        return sorted(ctx.textFile("text.txt", 4)
                      .flatMap(lambda line: line.split())
                      .map(lambda w: (w, 1))
                      .reduceByKey(operator.add, 2)   # stage 1: read+write
                      .map(lambda kv: (kv[1], 1))
                      .reduceByKey(operator.add, 2)   # stage 2: final
                      .collect())

    cfg = dict(concurrency=4, flush_records=1, duplicate_prob=0.2,
               visibility_timeout_s=0.5, drain_timeout_s=8.0,
               pipeline_stages=pipelined)
    clean = three_stage(FlintContext("flint", FlintConfig(**cfg)))
    faulty = FlintContext("flint", FlintConfig(**cfg),
                          fault_plan={(1, 0): {"fail_after_records": 1},
                                      (1, 1): {"fail_after_records": 1}},
                          elastic_retries=0)
    assert three_stage(faulty) == clean == [(100, 7), (200, 1), (300, 1)]


@pytest.mark.parametrize("pipelined", [True, False])
def test_mid_pipeline_groupby_retry_is_deterministic(pipelined):
    """Same, for group mode: value lists collect in arrival order, which
    differs across attempts — the drain sorts them before the task
    re-emits records that embed them."""
    cfg = dict(concurrency=4, flush_records=1, duplicate_prob=0.2,
               visibility_timeout_s=0.5, drain_timeout_s=8.0,
               pipeline_stages=pipelined)
    data = [(i % 4, i) for i in range(24)]

    def query(ctx):
        out = (ctx.parallelize(data, 3)
               .groupByKey(2)                         # stage 1: read+write
               .map(lambda kv: (len(kv[1]), sorted(kv[1])))
               .groupByKey(2)                         # stage 2: final
               .collect())
        # a group's value order carries no guarantee — compare multisets
        return sorted((k, sorted(v)) for k, v in out)

    clean = query(FlintContext("flint", FlintConfig(**cfg)))
    faulty = FlintContext("flint", FlintConfig(**cfg),
                          fault_plan={(1, 0): {"fail_after_records": 1},
                                      (1, 1): {"fail_after_records": 1}},
                          elastic_retries=0)
    assert query(faulty) == clean


@pytest.mark.parametrize("pipelined", [True, False])
def test_chained_producer_link_failure_resumes_from_cursor(pipelined):
    """A chained producer whose SECOND link dies retries from its last
    continuation cursor: the completed link's (src, seq) messages stay
    untouched and only the failed link replays — byte-identical, since
    in-link flush boundaries are record-count-based."""
    ctx = FlintContext("flint",
                       FlintConfig(concurrency=4, pipeline_stages=pipelined,
                                   max_records_per_invoke=35,
                                   flush_records=10, duplicate_prob=0.2,
                                   visibility_timeout_s=0.5,
                                   drain_timeout_s=8.0),
                       fault_plan={(0, 1): {"fail_on_link": 2}},
                       elastic_retries=0)
    assert wordcount(ctx) == EXPECTED
    stats = ctx.last_scheduler.stage_stats[0]
    assert stats["chained"] > 0
    assert stats["attempts"] >= 5  # 4 tasks + the link-2 retry


def test_drain_stall_times_out_despite_own_redeliveries():
    """A batch made purely of the drain's own lapsed-claim redeliveries is
    not progress: with a stuck producer (no EOS ever), the inactivity
    timeout must still fire instead of being reset forever."""
    import threading
    from repro.core.executors import (FlintConfig as FC, LambdaSim,
                                      _drain_shuffle)
    from repro.core.dag import ShuffleRead

    cfg = FC(shuffle_backend="sqs", visibility_timeout_s=0.2,
             drain_timeout_s=1.0)
    ledger = CostLedger()
    store = ObjectStoreSim(ledger)
    sqs = SQSSim(ledger, visibility_timeout=cfg.visibility_timeout_s)
    env = LambdaSim(cfg, ledger, store, sqs)
    from repro.core.shuffle import pack_batch, queue_name
    q8 = queue_name(8, 0)
    sqs.create_queue(q8)
    for body in pack_batch([(1, 1), (2, 2)]):
        sqs.send_batch(q8, [Message(body, 0, "s0t0")])
    # no EOS: the producer is permanently stuck

    err = []
    def drain():
        try:
            _drain_shuffle(ShuffleRead([(8, "group")], 0), env, {"8": 1})
        except Exception as e:  # noqa: BLE001
            err.append(e)
    t = threading.Thread(target=drain, daemon=True)
    t.start()
    t.join(8.0)
    assert not t.is_alive(), "drain hung: own redeliveries reset the deadline"
    assert err and isinstance(err[0], TimeoutError)
    sqs.close()


def test_consumer_retry_when_attempt_holds_messages_in_flight():
    """executor-level: a drain that received everything but died without
    acking leaves the queue refillable — a fresh drain completes after
    the visibility deadline lapses."""
    from repro.core.executors import (FlintConfig as FC, LambdaSim,
                                      _drain_shuffle)
    from repro.core.dag import ShuffleRead

    cfg = FC(shuffle_backend="sqs", visibility_timeout_s=0.3,
             drain_timeout_s=5.0)
    ledger = CostLedger()
    store = ObjectStoreSim(ledger)
    sqs = SQSSim(ledger, visibility_timeout=cfg.visibility_timeout_s)
    env = LambdaSim(cfg, ledger, store, sqs)
    from repro.core.shuffle import pack_batch, queue_name
    q7 = queue_name(7, 0)
    sqs.create_queue(q7)
    for body in pack_batch([(i, i) for i in range(50)]):
        sqs.send_batch(q7, [Message(body, 0, "s0t0")])
    sqs.send_batch(q7, [Message(b"", 1, "s0t0", kind="eos")])

    read = ShuffleRead([(7, "group")], 0)
    out1, _ack1 = _drain_shuffle(read, env, {"7": 1})
    # first attempt "dies" here: _ack1 never called, messages in flight
    out2, ack2 = _drain_shuffle(read, env, {"7": 1})
    assert out1[(7, "group")] == out2[(7, "group")]
    ack2()
    assert sqs.inflight_len(q7) == 0


# --------------------------------------------------- serde regressions


def test_serde_preserves_kwdefaults():
    def f(x, *, k=3, label="v"):
        return (x + k, label)

    g = serde.loads_fn(serde.dumps_fn(f))
    assert g(1) == (4, "v")
    assert g(1, k=10, label="w") == (11, "w")


def test_serde_self_referential_function():
    def fact(n):
        return 1 if n <= 1 else n * fact(n - 1)

    g = serde.loads_fn(serde.dumps_fn(fact))
    assert g(6) == 720


def test_serde_mutually_recursive_functions():
    def is_even(n):
        return True if n == 0 else is_odd(n - 1)

    def is_odd(n):
        return False if n == 0 else is_even(n - 1)

    g = serde.loads_fn(serde.dumps_fn(is_even))
    assert g(10) is True and g(7) is False


def test_serde_self_referential_closure():
    def make():
        def rec(n):
            return 0 if n == 0 else rec(n - 1) + 1
        return rec

    g = serde.loads_fn(serde.dumps_fn(make()))
    assert g(5) == 5


def _module_weight(v):
    # module-level on purpose: the recursive reference is a GLOBAL, and it
    # appears only inside the generator expression's nested code object
    if isinstance(v, (list, tuple)):
        return sum(_module_weight(x) for x in v) + len(v)
    return v


def test_serde_captures_globals_referenced_inside_comprehensions():
    """A global called only from a comprehension/genexpr lives in the
    NESTED code object's co_names; packing must walk nested code or the
    shipped function dies with NameError."""
    g = serde.loads_fn(serde.dumps_fn(_module_weight))
    assert g([1, [2, 3]]) == 1 + (2 + 3 + 2) + 2


def test_serde_recursive_fn_runs_on_executor():
    def weight(n):
        return 1 if n <= 1 else weight(n - 1) + 1

    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    out = dict(ctx.parallelize([(i % 3, i) for i in range(12)], 2)
               .map(lambda kv: (kv[0], weight(kv[1] % 4)))
               .reduceByKey(operator.add, 2).collect())
    assert sum(out.values()) == sum(max(1, i % 4) for i in range(12))


# ------------------------------------------------ oversized-record spill


def test_pack_records_spills_oversized_record():
    store = ObjectStoreSim(CostLedger())

    def spill(blob):
        key = "_spill/test"
        store.put(key, blob)
        return key

    big = ("k", "x" * 400_000)  # single pickle far over 256 KiB
    bodies = pack_records([("a", 1), big, ("b", 2)], spill=spill)
    assert all(len(b) <= 256 * 1024 for b in bodies)
    out = [r for b in bodies for r in unpack_records(b, store)]
    assert out == [("a", 1), big, ("b", 2)]
    # without a store the pointer cannot resolve
    ptr_body = pack_records([big], spill=spill)[0]
    with pytest.raises(ValueError):
        unpack_records(ptr_body)
    assert isinstance(pickle.loads(ptr_body[4:]), SpillPointer)


def test_oversized_record_rides_shuffle_end_to_end():
    """A >256 KiB record used to make every send_batch retry raise
    ValueError — now it spills to the object store and the consumer
    resolves the pointer."""
    big = "x" * 400_000
    # the 256 KiB cap is a QUEUE property — the S3 exchange ships batches
    # this size whole, so pin the transport the spill path exists for
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="sqs"))
    out = dict(ctx.parallelize([("big", big), ("small", "y")] * 2, 2)
               .groupByKey(2).collect())
    assert out["big"] == [big, big]
    assert out["small"] == ["y", "y"]
    # spill actually happened — and the job-end GC reclaimed every key
    assert ctx.last_scheduler.gc_report.get("_spill/", 0) > 0
    assert not ctx.store.list("_spill/")


# ----------------------------------------------- barrier-mode teardown


def test_barrier_stage_failure_closes_sqs_sim():
    """Barrier mode now tears the transport down on StageFailure like the
    pipelined path, so blocked consumers are released immediately instead
    of lingering up to drain_timeout_s in the thread pool."""
    cfg = FlintConfig(concurrency=4, pipeline_stages=False,
                      max_task_retries=0)
    ctx = FlintContext("flint", cfg)
    ctx.upload("text.txt", TEXT)
    rdd = (ctx.textFile("text.txt", 2).flatMap(lambda line: line.split())
           .map(lambda w: (w, 1)).reduceByKey(operator.add, 2))
    plan = build_plan(rdd, "collect")
    sched = FlintScheduler(cfg, ctx.ledger, ctx.store,
                           fault_plan={(0, 0): {"fail_attempts": 99}})
    with pytest.raises(StageFailure):
        sched.run(plan)
    assert sched.sqs.closed
    sched.shutdown()
