"""The counters kept at the engine's layer boundaries: the rows the
grouped sum hands the device before and after padding, the duplicates a
drain drops (recorded on its span), and the task response without the
drain tallies nothing read."""

import operator

import numpy as np
import pytest

from repro.core import FlintConfig, FlintContext
from repro.core.costs import CostLedger
from repro.core.dag import ShuffleRead
from repro.core.executors import FlintConfig as FC, LambdaSim, _drain_shuffle
from repro.core.queues import Message, ObjectStoreSim, SQSSim
from repro.core.shuffle import pack_batch, queue_name
from repro.kernels import ops


@pytest.mark.parametrize("n, padded", [(1, 1024), (1023, 1024),
                                       (1025, 2048), (8192, 8192)])
def test_grouped_reduce_counts_rows_and_padded_rows(n, padded):
    vals = np.arange(n, dtype=np.int64) % 7
    ids = np.arange(n) % 5
    stats = {}
    got = ops.grouped_reduce(vals, ids, 5, stats=stats)
    assert stats == {"kernel_calls": 1, "device_rows": n,
                     "device_padded_rows": padded,
                     "device_sum_columns": 1, "device_limb_columns": 1}
    assert got.tolist() == [int(vals[ids == g].sum()) for g in range(5)]
    ops.grouped_reduce(vals, ids, 5, stats=stats)
    assert stats["device_rows"] == 2 * n
    assert stats["device_padded_rows"] == 2 * padded


def test_grouped_reduce_counts_no_rows_it_hands_back():
    stats = {}
    vals = np.array([2**62, 2**62, 1], dtype=np.int64)
    assert ops.grouped_reduce(vals, np.zeros(3), 1, stats=stats) is None
    assert stats == {"device_fallbacks": 1}


def test_drain_records_a_planted_duplicate_on_its_span(program_trace):
    cfg = FC(shuffle_backend="sqs", visibility_timeout_s=0.3,
             drain_timeout_s=5.0)
    ledger = CostLedger()
    sqs = SQSSim(ledger, visibility_timeout=cfg.visibility_timeout_s)
    env = LambdaSim(cfg, ledger, ObjectStoreSim(ledger), sqs)
    q = queue_name(4, 0)
    sqs.create_queue(q)
    (body,) = pack_batch([(1, 10), (2, 20)])
    for _ in range(2):  # the same (src, seq) batch, delivered twice
        sqs.send_batch(q, [Message(body, 0, "s0t0")])
    sqs.send_batch(q, [Message(b"", 1, "s0t0", kind="eos")])

    (out, ack), events = program_trace(
        lambda: _drain_shuffle(ShuffleRead([(4, "agg")], 0), env,
                               {"4": 1}))
    ack()
    assert out[(4, "agg")] == {1: 10, 2: 20}
    (drain,) = [e for e in events if e[0] == "flint.shuffle.drain"]
    assert drain[4] == {"duplicates": 1}
    folds = [e for e in events if e[0] == "flint.shuffle.fold"]
    assert len(folds) == 1  # the duplicate is dropped before the fold
    assert drain[1] <= folds[0][1] < folds[0][2] <= drain[2]


def test_task_responses_carry_no_drain_tallies():
    ctx = FlintContext("flint", FlintConfig(concurrency=4,
                                            shuffle_backend="sqs"))
    ctx.upload("t.txt", b"a b\na c\n" * 50)
    responses = []
    make = ctx._make_scheduler

    def spy():
        sched = make()
        invoke = sched.lam.invoke

        def recorded(payload):
            resp = invoke(payload)
            responses.append(resp)
            return resp
        sched.lam.invoke = recorded
        return sched
    ctx._make_scheduler = spy
    out = dict(ctx.textFile("t.txt", 2).flatMap(str.split)
               .map(lambda w: (w, 1)).reduceByKey(operator.add, 2)
               .collect())
    assert out == {"a": 100, "b": 50, "c": 50}
    assert len(responses) == 4
    for resp in responses:
        assert resp["status"] == "ok"
        assert not {"messages", "duplicates", "records"} & set(resp["stats"])


def test_containers_start_cold_then_warm():
    ledger = CostLedger()
    env = LambdaSim(FC(), ledger, ObjectStoreSim(ledger), SQSSim(ledger))
    assert env._acquire_container() is True
    env._release_container()
    assert env._acquire_container() is False
    assert env._acquire_container() is True
