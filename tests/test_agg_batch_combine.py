"""The shuffle writer's column-wise map-side combine: grouped partials
handed over as a ``KVBatch`` that states its slot ops fold into the same
combine state, flush at the same records and pack into the same wire
bodies, with the same sequence ids, as their records added one by one."""

import math
from decimal import Decimal

import pytest

from repro.core import FlintConfig, FlintContext
from repro.core.costs import CostLedger
from repro.core.dag import ShuffleWrite
from repro.core.executors import LambdaSim, _ShuffleWriter
from repro.core.queues import ObjectStoreSim, SQSSim
from repro.core.shuffle import SLOT_MERGE, KVBatch, unpack_batch
from repro.sql import Schema, col, count_, lit, max_, min_, sum_

BACKENDS = ["sqs", "s3"]
NPARTS = 5


def merge_of(ops):
    """The SQL map-side combine's merge (sql/lower.py) for ``ops``."""
    def merge(a, b):
        return tuple(SLOT_MERGE[op](x, y) for op, x, y in zip(ops, a, b))
    return merge


def writer(backend, ops, flush_records=20_000, schema=None):
    """A writer on a fresh simulated environment whose transport records
    what it is handed: (partition, src, first seq, bodies)."""
    cfg = FlintConfig(flush_records=flush_records)
    ledger = CostLedger()
    env = LambdaSim(cfg, ledger, ObjectStoreSim(ledger), SQSSim(ledger))
    sent = []
    env.transports.get(backend).send = (
        lambda sid, p, src, seq, bodies: sent.append((p, src, seq, bodies)))
    write = ShuffleWrite(shuffle_id=1, nparts=NPARTS, mode="agg",
                         combine_fn=merge_of(ops), transport=backend,
                         batch_schema=schema)
    return _ShuffleWriter(write, env, "s0t0", None), sent


def drive(backend, ops, feed, flush_records=20_000, schema=None):
    """Everything one task's writer sends for ``feed``, with its stats."""
    w, sent = writer(backend, ops, flush_records, schema)
    w.add_all(feed)
    w.flush()
    return {"sent": sent, "out_stats": w.out_stats, "seq": w.seq,
            "counts": (w.combine_batch_records, w.combine_row_records)}


def as_records(feed):
    out = []
    for item in feed:
        out.extend(item.iter_rows() if isinstance(item, KVBatch) else [item])
    return out


def assert_same_wire(feed, backend, ops, flush_records=20_000, schema=None):
    """The batch fold of ``feed`` sends what adding its records one by
    one sends; returns the batch fold's run."""
    got = drive(backend, ops, feed, flush_records, schema)
    want = drive(backend, ops, as_records(feed), flush_records, schema)
    assert got["sent"] == want["sent"]
    assert got["out_stats"] == want["out_stats"]
    assert got["seq"] == want["seq"]
    n = len(as_records(feed))
    assert want["counts"] == (0, n)
    assert sum(got["counts"]) == n
    return got


def partials(keys, slot_rows, ops):
    """One chunk's grouped partials as the fused operator emits them."""
    kcols = [list(c) for c in zip(*keys)] if keys and keys[0] else []
    return KVBatch(kcols, [list(c) for c in zip(*slot_rows)], ops=ops)


def decoded(run):
    return [r for _p, _src, _seq, bodies in run["sent"]
            for b in bodies for r in unpack_batch(b)]


def stream(n_chunks=6, per_chunk=23, pool=40):
    """Chunks of distinct (int, str) keys drawn from a small pool, so keys
    recur across chunks, with (sum, min, max, sum) partials."""
    out = []
    for c in range(n_chunks):
        keys = []
        for j in range(per_chunk):
            i = (c * 17 + j * 7) % pool
            k = (i % 9, "k%d" % (i // 9))
            if k not in keys:
                keys.append(k)
        rows = [(len(k[1]) * 1000 + c, c - j, (c * j) % 11, 1)
                for j, k in enumerate(keys)]
        out.append((keys, rows))
    return out


OPS = ["sum", "min", "max", "sum"]
SCHEMA = ("t(i,s)", "t(i,i,i,i)")


@pytest.mark.parametrize("flush_records", [20_000, 7],
                         ids=["one-flush", "splits-batches"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_batch_fold_matches_record_fold(backend, flush_records):
    feed = [partials(k, r, OPS) for k, r in stream()]
    got = assert_same_wire(feed, backend, OPS, flush_records, SCHEMA)
    assert got["counts"] == (len(as_records(feed)), 0)
    if flush_records == 7:
        # 7 does not divide the chunks' new-key counts: flushes land
        # inside batches, and every partition flushed more than once
        assert max(got["seq"].values()) > 1
        assert len(got["sent"]) > NPARTS


@pytest.mark.parametrize("flush_records", [20_000, 3],
                         ids=["one-flush", "splits-batches"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_row_record_between_batches(backend, flush_records):
    """A row-path record (a chunk that fell back) between two batches, on
    a key the batches hold and on a new one, folds into the same state."""
    (k1, r1), (k2, r2) = stream(2, 12, 20)
    h = next(i for i, k in enumerate(k1) if k not in k2)
    held = k1[h]
    feed = [partials(k1, r1, OPS), (held, (5, -50, 50, 1)),
            (("new", "row"), (1, 1, 1, 1)),
            partials(k2 + [("new", "row"), held],
                     r2 + [(2, 0, 9, 1), (7, 3, 99, 1)], OPS)]
    got = assert_same_wire(feed, backend, OPS, flush_records)
    assert got["counts"] == (len(k1) + len(k2) + 2, 2)
    out = dict(decoded(got))
    if flush_records == 20_000:
        assert out[held] == tuple(SLOT_MERGE[op](SLOT_MERGE[op](a, b), c)
                                  for op, a, b, c in zip(
                                      OPS, r1[h], (5, -50, 50, 1),
                                      (7, 3, 99, 1)))


@pytest.mark.parametrize("order", ["int-first", "float-first",
                                   "bool-first"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_equal_keys_of_other_types_route_together(backend, order):
    """1, 1.0 and True compare equal and route as 1: whichever type comes
    first, batch or record, they fold into one entry, as one by one."""
    firsts = {"int-first": [1, 2], "float-first": [1.0, 2.5],
              "bool-first": [True, False]}[order]
    feed = [partials([(x,) for x in firsts], [(10,), (20,)], ["sum"]),
            ((1.0,), (1,)), ((True,), (2,)),
            partials([(1,), (True,)], [(100,), (1000,)], ["sum"]),
            partials([(1.0,)], [(10000,)], ["sum"])]
    got = assert_same_wire(feed, backend, ["sum"])
    ones = [(p, r) for p, _s, _q, bodies in got["sent"]
            for b in bodies for r in unpack_batch(b) if r[0] == (1,)]
    assert len(ones) == 1 and ones[0][1][1] == (11113,)


@pytest.mark.parametrize("backend", BACKENDS)
def test_keys_that_route_apart_are_not_memoized_together(backend):
    """Decimal("1.0") == Decimal("1.00") == 1, yet they pickle, and so
    route, apart: such keys never share the batch fold's route memo."""
    feed = [partials([(1,), (2,)], [(1,), (2,)], ["sum"]),
            partials([(Decimal("1.0"),), (Decimal("2.00"),)],
                     [(10,), (20,)], ["sum"]),
            ((Decimal("1.00"),), (100,)),
            partials([(1,), (Decimal("1.0"),)], [(1000,), (10000,)],
                     ["sum"])]
    got = assert_same_wire(feed, backend, ["sum"])
    assert got["counts"] == (2, 5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sum_past_2_63_stays_exact(backend):
    big = 2**62 + 12345
    feed = [partials([("a",), ("b",)], [(big, 1), (-big, 1)],
                     ["sum", "sum"]) for _ in range(5)]
    got = assert_same_wire(feed, backend, ["sum", "sum"], schema=(
        "t(s)", "t(i,i)"))
    out = dict(decoded(got))
    assert out[("a",)] == (5 * big, 5) and out[("b",)] == (-5 * big, 5)
    assert out[("a",)][0] > 2**63


@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_min_max_with_nan_match_merge(backend, op):
    """Python's min/max keep the FIRST operand on NaN: the fold applies
    them in merge's argument order."""
    nan = float("nan")
    cols = [(nan, 1.0, -0.0), (2.0, nan, 0.0), (0.5, 3.0, nan),
            (nan, nan, -1.0)]
    feed = [partials([("x",), ("y",), ("z",)], [(a,), (b,), (c,)], [op])
            for a, b, c in cols]
    got = assert_same_wire(feed, backend, [op])
    merge = merge_of([op])
    for key, i in ((("x",), 0), (("y",), 1), (("z",), 2)):
        want = (cols[0][i],)
        for row in cols[1:]:
            want = merge(want, (row[i],))
        have = dict(decoded(got))[key]
        assert (math.isnan(have[0]) and math.isnan(want[0])
                or have == want)


@pytest.mark.parametrize("backend", BACKENDS)
def test_global_aggregate_partials(backend):
    """A global aggregate's partials have no key column: every key ()."""
    feed = [partials([()], [(3, 1)], ["sum", "sum"]) for _ in range(4)]
    got = assert_same_wire(feed, backend, ["sum", "sum"])
    assert decoded(got) == [((), (12, 4))]


@pytest.mark.parametrize("backend", BACKENDS)
def test_retried_task_emits_identical_bodies(backend, monkeypatch):
    """A map task killed after some flushes landed re-runs from scratch:
    every (partition, src, seq) it sends again carries the same bodies,
    so (src, seq) dedup absorbs the first attempt's, and the answer is
    the fault-free one."""
    from repro.core import shuffle as S

    sends: dict = {}
    for cls in (S.SQSTransport, S.S3ExchangeTransport):
        orig = cls.send

        def send(self, sid, p, src, seq, bodies, _orig=orig):
            for i, b in enumerate(bodies):
                sends.setdefault((sid, p, src, seq + i), []).append(b)
            return _orig(self, sid, p, src, seq, bodies)
        monkeypatch.setattr(cls, "send", send)

    lines = "".join(f"{i % 37},{(i * 7) % 13},k{i % 5}\n" for i in range(600))
    schema = Schema([("a", "int"), ("b", "int"), ("c", "str")])

    def run(plan):
        cfg = FlintConfig(shuffle_backend=backend, concurrency=4,
                          flush_records=9, vector_batch_rows=50,
                          vectorize=True, retry_base_s=0.001,
                          retry_cap_s=0.01)
        ctx = FlintContext(config=cfg, fault_plan=plan)
        ctx.upload("t.csv", lines.encode())
        df = ctx.read_csv("t.csv", schema, 2)
        rows = sorted(df.where(col("b") != lit(3)).groupBy("a", "c")
                      .agg(sum_(col("b")).alias("s"), count_().alias("n"),
                           min_(col("b")).alias("lo"),
                           max_(col("b")).alias("hi")).collect())
        return rows, ctx.last_scheduler.device_stats

    clean, stats = run(None)
    assert stats["combine_batch_records"] and not stats["combine_row_records"]
    sends.clear()
    retried, _ = run({(0, 1): {"fail_after_records": 160}})
    assert retried == clean
    again = [bs for bs in sends.values() if len(bs) > 1]
    assert again, "the killed attempt flushed nothing before it died"
    assert all(b == bs[0] for bs in again for b in bs)
