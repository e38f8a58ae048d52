"""``shuffle.combine_batch_pct`` read from a ``device_stats`` dict, and
from the engine's own counters after each cell's queries on the CPU."""

import pytest

from bench import harness

READ = harness.reader("shuffle.combine_batch_pct")
CELLS = ["taxi-sqs.agg-hour", "taxi-sqs.agg-dayhour", "tpch-sqs.q1-q6"]


@pytest.mark.parametrize("stats, want", [
    ({"combine_batch_records": 40, "combine_row_records": 0}, 100.0),
    ({"combine_batch_records": 30, "combine_row_records": 10}, 75.0),
    ({"combine_batch_records": 0, "combine_row_records": 8}, 0.0),
    ({"combine_batch_records": 0, "combine_row_records": 0}, None),
    ({"kernel_calls": 3, "fused_chunks": 3}, None),
], ids=["all-batch", "mixed", "all-row", "none-counted", "parent-program"])
def test_reads_the_device_stats(stats, want):
    assert READ({"device_stats": stats}) == want


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_folds_its_partials_column_wise(cell):
    _bench, _cell, config, data_mod, mix = harness.cell_parts(cell)
    dep = harness.load_module(
        harness.BENCH / "deployments" / f"{config['deployment']}.py"
    ).open_deployment(config, data_mod.TABLE,
                      data_mod.generate(2400, 2**31 + 5))
    try:
        client = dep.clients[0]
        for query in mix["queries"]:
            query.build(lambda: client.read_csv(
                data_mod.TABLE, list(data_mod.SCHEMA),
                config["input_partitions"])).collect()
            assert READ({"device_stats": dep.device_stats(client)}) == 100.0
    finally:
        dep.close()
