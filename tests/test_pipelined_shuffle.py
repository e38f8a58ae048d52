"""Pipelined map→reduce shuffle execution with the EOS protocol
(docs/eos_shuffle.md): consumers are launched concurrently with their
producers, drain as messages arrive, and terminate on per-producer
end-of-stream control messages instead of a post-hoc count table."""

import operator
import pickle
import zlib

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import FlintConfig, FlintContext
from repro.core.dag import ShuffleWrite
from repro.core.executors import _ShuffleWriter
from repro.core.queues import pack_records, unpack_records

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


def test_pipelined_is_the_default():
    assert FlintConfig().pipeline_stages is True


def test_barrier_mode_still_works():
    ctx = FlintContext("flint", FlintConfig(concurrency=8,
                                            pipeline_stages=False))
    assert wordcount(ctx) == EXPECTED


def test_eos_under_chaining():
    """A chained producer must not emit EOS until its last link; consumers
    still terminate with the full record set."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            max_records_per_invoke=35,
                                            flush_records=10))
    assert wordcount(ctx) == EXPECTED
    assert ctx.last_scheduler.stage_stats[0]["chained"] > 0


def test_retry_after_partial_eosless_failure():
    """A producer that dies after flushing some messages (but before EOS)
    is retried with the same identity: the retry re-emits the same
    sequence ids (deduped) plus the closing EOS."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4, flush_records=10),
                       fault_plan={(0, 1): {"fail_after_records": 50}})
    assert wordcount(ctx) == EXPECTED


def test_speculation_duplicate_eos_dedup():
    """A speculative duplicate of a straggling producer emits a second,
    identical EOS per partition — consumers dedup by producer id."""
    ctx = FlintContext("flint", FlintConfig(concurrency=8,
                                            speculation_factor=2.0,
                                            speculation_min_done=2),
                       fault_plan={(0, 0): {"straggle_s": 0.8}})
    assert wordcount(ctx, nparts=8, red_parts=4) == EXPECTED
    assert ctx.last_scheduler.stage_stats[0]["speculated"] >= 1


def test_empty_partitions_terminate():
    """Producers send EOS to EVERY partition (total 0 where they wrote
    nothing), so reducers of empty partitions terminate too."""
    ctx = FlintContext("flint", FlintConfig(concurrency=8))
    data = [("only-key", 1)] * 40
    out = dict(ctx.parallelize(data, 3)
               .reduceByKey(operator.add, 6).collect())
    assert out == {"only-key": 40}


def test_pipelined_at_least_once_dedup():
    """Duplicated deliveries (data AND EOS) are absorbed by seq-id /
    producer-id dedup under the streaming drain."""
    ctx = FlintContext("flint", FlintConfig(concurrency=8, flush_records=20,
                                            duplicate_prob=0.3))
    assert wordcount(ctx) == EXPECTED


def test_pipelined_s3_shuffle_backend():
    """EOS markers work over the object-store transport too."""
    ctx = FlintContext("flint", FlintConfig(concurrency=8,
                                            shuffle_backend="s3",
                                            flush_records=20))
    assert wordcount(ctx) == EXPECTED


@given(nparts=st.integers(1, 6), red_parts=st.integers(1, 5))
@settings(max_examples=6, deadline=None)
def test_barrier_pipelined_result_equality(nparts, red_parts):
    """Property: both execution modes produce identical results on the
    same query, for any partitioning."""
    barrier = wordcount(
        FlintContext("flint", FlintConfig(concurrency=8,
                                          pipeline_stages=False)),
        nparts, red_parts)
    pipelined = wordcount(
        FlintContext("flint", FlintConfig(concurrency=8,
                                          pipeline_stages=True)),
        nparts, red_parts)
    assert barrier == pipelined == EXPECTED


class _CountedPickles:
    """Record whose pickling is observable — for asserting pack_records
    serializes each record exactly once."""

    dumps = 0

    def __init__(self, payload):
        self.payload = payload

    def __reduce__(self):
        _CountedPickles.dumps += 1
        return (_new_counted, (self.payload,))


def _new_counted(payload):
    obj = _CountedPickles.__new__(_CountedPickles)
    obj.payload = payload
    return obj


def test_pack_records_pickles_each_record_exactly_once():
    _CountedPickles.dumps = 0
    records = [_CountedPickles(("key", i, "x" * 50)) for i in range(500)]
    bodies = pack_records(records)
    assert _CountedPickles.dumps == 500
    out = [r for b in bodies for r in unpack_records(b)]
    assert [r.payload for r in out] == [r.payload for r in records]


def test_pack_records_splits_on_cap():
    records = [("k%d" % i, "v" * 60_000) for i in range(40)]
    bodies = pack_records(records)
    assert len(bodies) > 1
    assert all(len(b) <= 256 * 1024 for b in bodies)
    out = [r for b in bodies for r in unpack_records(b)]
    assert out == records


def test_partitioning_is_stable_and_seed_independent():
    """crc32-of-pickled-key routing: identical across writer instances and
    independent of PYTHONHASHSEED, as re-invoked Lambdas require."""
    w = ShuffleWrite(shuffle_id=999, nparts=7, mode="group")
    a = _ShuffleWriter(w, None, "s0t0", None)
    b = _ShuffleWriter(w, None, "s0t1", None)
    for key in ["alpha", ("month", 3, "cash"), 42, ("nested", ("t", 1))]:
        expect = zlib.crc32(
            pickle.dumps(key, protocol=pickle.HIGHEST_PROTOCOL)) % 7
        assert a._partition_of(key) == b._partition_of(key) == expect


def test_pipelined_join_and_groupby():
    ctx = FlintContext("flint", FlintConfig(concurrency=8))
    left = ctx.parallelize([(i % 5, f"L{i}") for i in range(20)], 3)
    right = ctx.parallelize([(i % 5, f"R{i}") for i in range(10)], 2)
    assert len(left.join(right, 4).collect()) == 40
    grouped = dict(ctx.parallelize([(i % 3, i) for i in range(12)], 2)
                   .groupByKey(3).collect())
    assert sorted(grouped[0]) == [0, 3, 6, 9]


def test_chained_links_report_records_in():
    """metered(): a chained (continuation) invocation reports the records
    it actually ingested — the pre-fix code reported 0 for every link that
    hit the lease instead of exhausting its input."""
    from repro.core.costs import CostLedger
    from repro.core.dag import SourceInput, TaskDef
    from repro.core.executors import LambdaSim, executor_main, serialize_task
    from repro.core.queues import ObjectStoreSim, SQSSim

    cfg = FlintConfig(max_records_per_invoke=10)
    ledger = CostLedger()
    store, sqs = ObjectStoreSim(ledger), SQSSim(ledger)
    store.put("t.txt", TEXT)
    env = LambdaSim(cfg, ledger, store, sqs)
    size = store.size("t.txt")
    task = TaskDef(0, 0, SourceInput("t.txt", 0, size, size), [], None)
    resp = executor_main(serialize_task(task, 0, {}), env)
    assert "continuation" in resp  # lease hit after 10 of 300 records
    assert resp["stats"]["records_in"] == 10


def test_equal_numeric_keys_co_partition():
    """1 == 1.0 == True must fold into one key even though their pickles
    differ — the stable partitioner canonicalizes before hashing."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4))
    out = dict(ctx.parallelize([(1, 10), (1.0, 5), (True, 1),
                                ((2, 3.0), 7), ((2, 3), 2)], 3)
               .reduceByKey(operator.add, 4).collect())
    cluster = dict(FlintContext("cluster", FlintConfig(concurrency=4))
                   .parallelize([(1, 10), (1.0, 5), (True, 1),
                                 ((2, 3.0), 7), ((2, 3), 2)], 3)
                   .reduceByKey(operator.add, 4).collect())
    assert out == cluster == {1: 16, (2, 3): 9}


def test_failed_sqs_consumer_recovers_via_redelivery():
    """A consumer that dies mid-task never acked its receives, so after
    the visibility timeout every message it read redelivers to its retry —
    the job completes instead of aborting (receives used to be
    destructive, making any consumer failure fatal)."""
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="sqs",
                                            visibility_timeout_s=0.5,
                                            drain_timeout_s=8.0),
                       fault_plan={(1, 0): {"fail_after_records": 1}},
                       elastic_retries=0)
    assert wordcount(ctx, nparts=2, red_parts=2) == EXPECTED
    assert ctx.last_scheduler.stage_stats[-1]["attempts"] >= 3  # 2 tasks + retry


def test_send_to_deleted_queue_is_dropped():
    """A losing speculative duplicate flushing after its stage completed
    must not resurrect deleted queues (and strand messages in them)."""
    from repro.core.costs import CostLedger
    from repro.core.queues import Message, SQSSim
    sqs = SQSSim(CostLedger())
    sqs.create_queue("q")
    sqs.delete_queue("q")
    sqs.send_batch("q", [Message(b"x", 0, "s0t0")])
    assert sqs.approx_len("q") == 0
    assert "q" not in sqs._queues


def test_pipelined_cost_report_still_pay_as_you_go():
    ctx = FlintContext("flint", FlintConfig(concurrency=8))
    wordcount(ctx)
    rep = ctx.cost_report()
    assert rep["lambda_requests"] >= 7
    # "auto" default: the planner resolves the transport per shuffle
    shuffle_requests = rep["sqs_requests"] + rep["s3_lists"]
    assert shuffle_requests > 0 and rep["total_usd"] > 0


def test_group_lists_follow_producer_order_not_arrival_order():
    """groupByKey value-lists are built in (src, seq) order: the first
    partition's producer finishing last changes nothing."""
    import time

    def slow_first(x):
        if x < 100:
            time.sleep(0.0005)
        return (x % 3, x)

    ctx = FlintContext("flint", FlintConfig(concurrency=8))
    got = dict(ctx.parallelize(range(400), 4).map(slow_first)
               .groupByKey(2).collect())
    assert got == {k: [x for x in range(400) if x % 3 == k]
                   for k in range(3)}
