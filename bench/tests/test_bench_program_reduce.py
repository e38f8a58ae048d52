"""The reduction of the program's spans on a synthetic three-thread trace
with a known answer."""

import pytest

from bench import program_spans as ps
from bench import trace as tr

MS = 1_000_000
C, X1, X2 = "host#0", "host#1", "host#2"  # client and two executor lines


def make(devices, spans, program=(), lines=None):
    return ps.Spans(tr.Trace(devices, spans), list(program),
                    lines if lines is not None else [C] * len(spans))


def three_threads(extra_dispatch=True):
    """A query on the client line with planning, a job, two dispatches, a
    merge and teardown; one task a line on two executor lines."""
    def sp(name, s, e, line, **stats):
        return (name, s * MS, e * MS, line, stats)
    program = [
        sp("flint.plan", 0, 5, C),
        sp("flint.job", 5, 90, C, job=1),
        sp("flint.dispatch", 10, 12, C, dispatch=1),
        sp("flint.dispatch", 12, 15, C, dispatch=2),
        sp("flint.merge", 80, 85, C),
        sp("flint.teardown", 90, 95, C),
        sp("flint.task", 25, 70, X1, dispatch=1),
        sp("flint.scan", 25, 30, X1),
        sp("flint.fused", 30, 60, X1),
        sp("flint.grouped_sum", 40, 50, X1),
        sp("flint.task", 20, 75, X2, dispatch=2),
        sp("flint.shuffle.drain", 20, 75, X2, duplicates=2),
        sp("flint.shuffle.wait", 30, 60, X2),
        sp("flint.shuffle.fold", 60, 70, X2),
    ]
    if extra_dispatch:  # sent, never ran: matched by id, not by order
        program.insert(4, sp("flint.dispatch", 15, 16, C, dispatch=3))
    spans = [("bench.window", 0, 100 * MS), ("bench.query.q", 0, 100 * MS)]
    return make({"/device:TPU:0": {tr.OP_LINE: []}}, spans, program)


def test_program_spans_known_answer():
    r = ps.reduce(three_threads())
    own, total = r["span_self_s"], r["span_total_s"]
    assert r["program_spans"] == 15
    assert own == pytest.approx({
        "flint.plan": 0.005, "flint.job": 0.085 - 0.006 - 0.005,
        "flint.dispatch": 0.006, "flint.merge": 0.005,
        "flint.teardown": 0.005, "flint.task": 0.010,
        "flint.scan": 0.005, "flint.fused": 0.020,
        "flint.grouped_sum": 0.010, "flint.shuffle.drain": 0.015,
        "flint.shuffle.wait": 0.030, "flint.shuffle.fold": 0.010})
    assert total["flint.task"] == pytest.approx(0.045 + 0.055)
    assert total["flint.fused"] == pytest.approx(0.030)
    assert total["flint.shuffle.drain"] == pytest.approx(0.055)
    # task 1 waited 25 - 12 ms, task 2 20 - 15 ms
    assert r["queue_wait_s"] == pytest.approx(0.013 + 0.005)
    assert r["duplicates"] == 2
    # the task self time (x1, 60-70 ms) plus the client's 95-100 ms
    assert r["unattributed_s"] == pytest.approx(0.010 + 0.005)


def test_per_query_numbers_of_the_known_answer():
    got = ps.per_query(ps.reduce(three_threads()), queries=2)
    assert got == pytest.approx({
        "plan.ms_per_query": 2.5,
        "scheduler.dispatch_ms_per_query": 3.0,
        "ingest.ms_per_query": 2.5,
        "fused_op.host_ms_per_query": 10.0,
        "grouped_sum.host_ms_per_query": 5.0,
        "shuffle.write_ms_per_query": 0.0,
        "shuffle.read_ms_per_query": 7.5,
        "shuffle.wait_ms_per_query": 15.0,
        "shuffle.fold_ms_per_query": 5.0,
        "driver.finish_ms_per_query": 5.0,
        "scheduler.queue_ms_per_query": 9.0,
        "host.unattributed_ms_per_query": 7.5,
        "shuffle.duplicates_per_query": 1.0})


def test_self_times_and_the_client_add_up_to_query_and_task_time():
    r = ps.reduce(three_threads(extra_dispatch=False))
    client_outside = r["unattributed_s"] - r["span_self_s"]["flint.task"]
    query = 0.100
    assert (sum(r["span_self_s"].values()) + client_outside
            == pytest.approx(query + r["span_total_s"]["flint.task"]))


def test_self_time_counts_spans_that_start_in_the_window():
    t = three_threads()
    t.trace.spans[0] = ("bench.window", 22 * MS, 100 * MS)
    r = ps.reduce(t)
    assert "flint.plan" not in r["span_self_s"]
    assert "flint.task" in r["span_self_s"]  # x1's task starts at 25 ms
    assert r["span_total_s"]["flint.task"] == pytest.approx(0.045)
    assert r["queue_wait_s"] == pytest.approx(0.013)


def test_idle_time_is_split_over_the_spans_open_in_a_gap():
    ops = [("a", 0, 20 * MS), ("b", 40 * MS, 100 * MS)]
    program = [("flint.shuffle.wait", 20 * MS, 40 * MS, X1, {}),
               ("flint.fused", 20 * MS, 40 * MS, X2, {})]
    r = ps.reduce(make({"/device:TPU:0": {tr.OP_LINE: ops}},
                       [("bench.window", 0, 100 * MS),
                        ("bench.query.q", 0, 100 * MS)], program))
    assert dict(r["idle_by_span"]) == pytest.approx(
        {"flint.shuffle.wait": 0.010, "flint.fused": 0.010})


def test_idle_gap_label_names_the_busiest_program_span():
    ops = [("a", 0, 20 * MS), ("b", 40 * MS, 60 * MS),
           ("c", 90 * MS, 100 * MS)]
    program = [("flint.shuffle.wait", 15 * MS, 45 * MS, X1, {}),
               ("flint.fused", 35 * MS, 42 * MS, X2, {})]
    r = ps.reduce(make({"/device:TPU:0": {tr.OP_LINE: ops}},
                       [("bench.window", 0, 100 * MS),
                        ("bench.query.q", 0, 100 * MS)], program))
    # [60, 90] has no program span open; [20, 40] holds 20 ms of the
    # wait and 5 ms of the fused operator
    assert r["idle_gaps"] == [
        ("bench.query.q", pytest.approx(0.030)),
        ("bench.query.q/flint.shuffle.wait", pytest.approx(0.020))]
    assert dict(r["idle_by_span"]) == pytest.approx(
        {"flint.shuffle.wait": 0.0175, "flint.fused": 0.0025})


def test_grouped_sum_programs_inside_host_spans():
    modules = [("jit__kernel_sums(1)", 11 * MS, 12 * MS),
               ("jit__kernel_sums(1)", 31 * MS, 33 * MS),
               ("jit__x64_sums(2)", 50 * MS, 52 * MS)]
    program = [("flint.grouped_sum", 10 * MS, 20 * MS, X1, {}),
               ("flint.grouped_sum", 30 * MS, 32 * MS, X2, {})]
    r = ps.reduce(make({"/device:TPU:0": {tr.MODULE_LINE: modules}},
                       [("bench.window", 0, 100 * MS)], program))
    assert r["grouped_sum_programs"] == 3
    assert r["grouped_sum_programs_in_span"] == 1


def test_without_program_spans_the_gaps_read_as_the_benchmark_reads_them():
    ops = [("a", 10 * MS, 20 * MS), ("b", 50 * MS, 60 * MS)]
    t = make({"/device:TPU:0": {tr.OP_LINE: ops}},
             [("bench.window", 0, 100 * MS), ("bench.query.q1", 0, 40 * MS),
              ("bench.query.q2", 40 * MS, 100 * MS)])
    r = ps.reduce(t)
    assert r["program_spans"] == 0 and r["idle_by_span"] == []
    assert r["idle_gaps"] == tr.reduce_trace(t.trace)["idle_gaps"]
    assert ps.per_query(r, queries=3) == {}
    assert ps.reduce(make({}, [("bench.window", 0, MS)])) is None
