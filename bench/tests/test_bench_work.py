"""The grouped sum's byte count and the table of peaks."""

import numpy as np
import pytest

from bench import harness, reference, work

DS = harness.dataset("tlc-yellow-2015")
MIX = harness.load_mix("agg-dayhour", DS)
SPEC = MIX["queries"][0]  # two sums over credit rows, by day-hour


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_partitions_match_the_engine_split():
    from repro.core import FlintConfig, FlintContext

    raw = DS.generate(5000, 11)
    ctx = FlintContext(config=FlintConfig(concurrency=4))
    ctx.upload(DS.TABLE, raw)
    sizes = (ctx.textFile(DS.TABLE, 8)
             .mapPartitions(lambda it: [sum(1 for _ in it)]).collect())
    assert sorted(sizes) == sorted(
        np.bincount(work.partition_of_rows(raw, 8), minlength=8).tolist())


def _row(pickup, payment, tip, total):
    r = [""] * len(DS.SCHEMA)
    ix = {n: i for i, (n, _) in enumerate(DS.SCHEMA)}
    r[ix["tpep_pickup_datetime"]] = pickup
    r[ix["payment_type"]] = payment
    r[ix["tip_amount"]] = tip
    r[ix["total_amount"]] = total
    return r


def test_bytes_by_hand():
    rows = ([_row("2015-01-01 01:00:00", "1", "1.00", "2.00")] * 3
            + [_row("2015-01-01 02:00:00", "2", "0.00", "2.00")])
    parts = np.array([0, 0, 1, 1])
    # two sums over the 3 credit rows; groups (0, "01-01 01"), (1, same)
    assert SPEC.sum_bytes(rows, parts) == 2 * (3 * 12 + 2 * 8)
    join = harness.load_mix("agg-hour", DS)["queries"][1]
    # the count-only side adds nothing
    assert join.sum_bytes(rows, parts) == 3 * 12 + 2 * 8


@pytest.mark.parametrize("batch_rows", [1024, 8192])
def test_rows_counted_whatever_the_chunking(monkeypatch, batch_rows):
    """The rows that the engine hands to the grouped sum, at any chunk
    size, are the rows the byte count charges 12 B each."""
    from repro.core import FlintConfig, FlintContext
    from repro.kernels import ops

    raw = DS.generate(6000, 3)
    rows = reference.parse(raw)
    parts = work.partition_of_rows(raw, 8)
    entered = []

    def counting(values, bucket_ids, n_buckets, **kw):
        vals = np.asarray(values, dtype=np.int64)
        entered.append(len(vals))
        out = np.zeros(n_buckets, dtype=np.int64)
        np.add.at(out, np.asarray(bucket_ids), vals)
        return out

    monkeypatch.setattr(ops, "grouped_reduce", counting)
    ctx = FlintContext(config=FlintConfig(
        concurrency=4, vector_backend="jax", vector_batch_rows=batch_rows))
    ctx.upload(DS.TABLE, raw)
    got = SPEC.build(lambda: ctx.read_csv(DS.TABLE, list(DS.SCHEMA),
                                          8)).collect()
    assert sorted(got) == SPEC.reference(rows)
    pay = [n for n, _ in DS.SCHEMA].index("payment_type")
    pickup = [n for n, _ in DS.SCHEMA].index("tpep_pickup_datetime")
    credit = sum(r[pay] == "1" for r in rows)
    assert sum(entered) == 2 * credit
    groups = len({(p, r[pickup][5:13]) for r, p in zip(rows, parts.tolist())
                  if r[pay] == "1"})
    assert SPEC.sum_bytes(rows, parts) == 2 * (credit * 12 + groups * 8)
