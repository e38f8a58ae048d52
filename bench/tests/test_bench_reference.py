"""The plain reference agrees with the engine's row path and its numpy
backend on every query of every traffic mix, and the dataset generator
is a pure function of its seed with the published layout."""

import pytest

from bench import harness, reference

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONFIGS = {c["name"]: harness.load_json(harness.ROOT / c["file"])
           for c in BENCH["configs"]}
MIXES = sorted({(CONFIGS[w["config"]]["dataset"], w["traffic"])
                for w in BENCH["workloads"]})
CASES = [(ds, mix, q.name) for ds, mix in MIXES
         for q in harness.load_mix(mix, harness.dataset(ds))["queries"]]
DATASETS = sorted({c["dataset"] for c in CONFIGS.values()})
ROWS = 3000
SEED = 2**31 + 29


def typed(rows):
    return sorted(tuple((type(v).__name__, v) for v in r) for r in rows)


@pytest.fixture(scope="module")
def tables():
    out = {}
    for ds in DATASETS:
        raw = harness.dataset(ds).generate(ROWS, SEED)
        out[ds] = raw, reference.parse(raw)
    return out


@pytest.mark.parametrize("engine", ["row", "numpy"])
@pytest.mark.parametrize("ds,mix,name", CASES)
def test_reference_equals_engine(tables, ds, mix, name, engine):
    from repro.core import FlintConfig, FlintContext

    data_mod = harness.dataset(ds)
    raw, rows = tables[ds]
    kw = ({"vectorize": False} if engine == "row"
          else {"vector_backend": "numpy"})
    ctx = FlintContext(config=FlintConfig(concurrency=8,
                                          shuffle_backend="sqs", **kw))
    ctx.upload(data_mod.TABLE, raw)
    query = next(q for q in harness.load_mix(mix, data_mod)["queries"]
                 if q.name == name)
    got = query.build(lambda: ctx.read_csv(data_mod.TABLE,
                                           list(data_mod.SCHEMA), 8)).collect()
    want = query.reference(rows)
    assert want, "the query must have an answer at this size"
    assert typed(got) == typed(want)


@pytest.mark.parametrize("ds", DATASETS)
def test_generator_is_seeded(ds):
    gen = harness.dataset(ds).generate
    a = gen(2000, -7)
    assert a == gen(2000, -7)
    assert a != gen(2000, 2**40)
    rows = reference.parse(a)
    assert len(rows) == 2000
    ev = reference.Evaluator(harness.dataset(ds).SCHEMA)
    for name, _ in harness.dataset(ds).SCHEMA:
        col = ev.column(name)
        for r in rows:
            col(r)  # every cell reads as its declared type


def test_tlc_yellow_layout():
    ds = harness.dataset("tlc-yellow-2015")
    assert [n for n, _ in ds.SCHEMA][:3] == [
        "VendorID", "tpep_pickup_datetime", "tpep_dropoff_datetime"]
    assert len(ds.SCHEMA) == 19
    rows = reference.parse(ds.generate(20000, 2**33 + 1))
    ix = {n: i for i, (n, _) in enumerate(ds.SCHEMA)}
    cents = [ix[n] for n in ("fare_amount", "extra", "mta_tax", "tip_amount",
                             "tolls_amount", "improvement_surcharge")]
    hours = [0] * 24
    for r in rows:
        assert r[ix["tpep_pickup_datetime"]][:5] == "2015-"
        assert r[ix["tpep_dropoff_datetime"]] > r[ix["tpep_pickup_datetime"]]
        assert r[ix["payment_type"]] in "1234"
        if r[ix["payment_type"]] != "1":
            assert r[ix["tip_amount"]] == "0.00"
        # the total is the sum of its parts to the cent
        assert round(float(r[ix["total_amount"]]) * 100) == sum(
            round(float(r[i]) * 100) for i in cents)
        hours[int(r[ix["tpep_pickup_datetime"]][11:13])] += 1
    # the hourly skew: the evening peak holds several times the 5 AM trough
    assert hours[19] > 4 * hours[5]
