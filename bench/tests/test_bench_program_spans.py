"""One small taxi query of each cell's mix, recorded on the CPU in the
profiled block of ``bench.program_spans``, which switches the program's
spans on: every span of the engine's query path is there, inside its
``bench.query.*`` span, and each task's span carries the id of the
dispatch that sent it."""

import pytest

from bench import harness
from bench import program_spans as ps

ROWS = 2400
SPANS = ("flint.plan", "flint.job", "flint.dispatch", "flint.task",
         "flint.scan", "flint.ingest", "flint.fused", "flint.grouped_sum",
         "flint.grouped_sum.fetch", "flint.shuffle.write",
         "flint.shuffle.send", "flint.shuffle.drain", "flint.shuffle.wait",
         "flint.shuffle.fold", "flint.merge", "flint.teardown")


@pytest.fixture
def recorded():
    """The loaded spans of one round of a cell's mix, run in the
    profiled block that switches the program's spans on."""
    def run(cell):
        _, _, config, data_mod, mix = harness.cell_parts(cell)
        assert config["engine"]["vector_backend"] == "jax"
        dep = harness.load_module(
            harness.BENCH / "deployments"
            / f"{config['deployment']}.py").open_deployment(
            config, data_mod.TABLE, data_mod.generate(ROWS, 2**31 + 9))
        tables = [lambda c=c: c.read_csv(data_mod.TABLE,
                                         list(data_mod.SCHEMA),
                                         config["input_partitions"])
                  for c in dep.clients]
        got: dict = {}
        with ps.profiled(True, keep=got):
            records, _, _ = harness.window(dep, tables, mix, 1, 0.5)
        dep.close()
        assert records and all(r["error"] is None for r in records)
        return got["spans"], len(records)
    return run


@pytest.mark.parametrize("cell", ["taxi-sqs.agg-hour",
                                  "taxi-sqs.agg-dayhour"])
def test_a_traced_query_has_every_span_inside_its_query(recorded, cell):
    loaded, n = recorded(cell)
    trace, program = loaded.trace, loaded.program
    names = {sp[0] for sp in program}
    assert set(SPANS) <= names
    queries = [(s, e) for name, s, e in trace.spans
               if name.startswith(ps.QUERY_PREFIX)]
    assert len(queries) == n
    for name, s, e, _, _ in program:
        assert any(qs <= s <= e <= qe for qs, qe in queries), name
    sent = {st["dispatch"]: (s, e) for name, s, e, _, st
            in program if name == "flint.dispatch"}
    tasks = [(s, st) for name, s, _, _, st in program
             if name == "flint.task"]
    assert tasks and len(sent) == len(tasks)
    for s, st in tasks:
        assert sent[st["dispatch"]][0] <= s
    # the client's line holds the query, its planning and its job
    (line,) = set(loaded.lines[i] for i, sp in enumerate(trace.spans)
                  if sp[0].startswith(ps.QUERY_PREFIX))
    assert {sp[3] for sp in program
            if sp[0] in ("flint.plan", "flint.job")} == {line}
