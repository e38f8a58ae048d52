"""The trace reduction on a synthetic trace with a known answer."""

import pytest

from bench import trace as tr

MS = 1_000_000


def make(devices=None, spans=None):
    return tr.Trace(devices=devices or {}, spans=spans or [])


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]


def test_reduce_known_answer():
    ops = [("fusion", 10 * MS, 20 * MS), ("bucket_reduce", 15 * MS,
                                           30 * MS),
           ("fusion", 50 * MS, 60 * MS),
           ("fusion", 95 * MS, 130 * MS)]  # runs past the window
    modules = [("jit__kernel_sums(3)", 10 * MS, 30 * MS),
               ("jit_other", 50 * MS, 60 * MS),
               ("jit__x64_sums(1)", 95 * MS, 130 * MS),
               ("jit__kernel_sums(3)", 150 * MS, 160 * MS)]  # outside
    spans = [("bench.window", 0, 100 * MS),
             ("bench.query.q1", 0, 70 * MS),
             ("bench.between_queries", 70 * MS, 72 * MS),
             ("bench.query.q2", 72 * MS, 100 * MS)]
    t = make({"/device:TPU:0": {tr.OP_LINE: ops, tr.MODULE_LINE: modules}},
             spans)
    r = tr.reduce_trace(t)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [10, 30] + [50, 60] + [95, 100] within the window
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["grouped_sum_s"] == pytest.approx(0.020 + 0.035)
    assert r["grouped_sum_programs"] == 2
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion": 0.010 + 0.010 + 0.035, "bucket_reduce": 0.015})
    # gaps: [0,10] q1, [30,50] q1, [60,95] mid 77.5 -> q2
    assert r["idle_gaps"] == [("bench.query.q2", pytest.approx(0.035)),
                              ("bench.query.q1", pytest.approx(0.020)),
                              ("bench.query.q1", pytest.approx(0.010))]


def test_busy_averages_devices_and_falls_back_to_modules():
    spans = [("bench.window", 0, 100 * MS)]
    t = make({"/device:TPU:0": {tr.OP_LINE: [("a", 0, 40 * MS)]},
              "/device:TPU:1": {tr.MODULE_LINE: [("jit_b", 0, 20 * MS)]}},
             spans)
    r = tr.reduce_trace(t)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["idle_gaps"] == [("bench.window", pytest.approx(0.060))]


def test_op_names_drop_layout_and_operands():
    assert tr.op_name("%copy = f32[8192,1]{1,0:T(8,128)S(1)} copy(f32[8192,1]"
                      "{0,1:T(1,128)} %bitcast.2)") == "%copy f32[8192,1]"
    assert tr.op_name("fusion") == "fusion"


def test_nothing_to_read():
    assert tr.reduce_trace(make(spans=[("bench.window", 0, 1)])) is None
    assert tr.reduce_trace(make({"/device:TPU:0": {}})) is None


def test_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.query.x"):
            jnp.arange(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = [n for n, _, _ in t.spans]
    assert names.count("bench.window") == 1 and "bench.query.x" in names
    (_, s0, e0), = [s for s in t.spans if s[0] == "bench.window"]
    (_, s1, e1), = [s for s in t.spans if s[0] == "bench.query.x"]
    assert s0 <= s1 <= e1 <= e0
