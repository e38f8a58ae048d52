"""The engine's span module (repro.core.spans): while tracing is off a
span is one shared no-op that allocates nothing and needs no jax; while
it is on, spans nest and carry their ids into a recorded trace, and a
held span ends where another span starts."""

import os
import subprocess
import sys
import time
import tracemalloc

from repro.core import spans

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_off_returns_the_one_shared_noop():
    assert not spans.enabled()
    got = {spans.span(name, **ids) for name, ids in (
        ("flint.task", {"job": 1, "task": 2, "dispatch": 3}),
        ("flint.scan", {}), ("anything", {"rows": 10}))}
    assert got == {spans.OFF}
    with spans.span("flint.shuffle.drain") as s:
        s.set_metadata(duplicates=1)
    assert spans.keep_held() is spans.OFF
    spans.hold("flint.shuffle.write")  # nothing held while off
    spans.release()


def test_off_allocates_nothing():
    def loop():
        for i in range(10_000):
            with spans.span("flint.scan"):
                pass
            with spans.span("flint.task", job=1, stage=2, task=3):
                pass
    loop()
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        loop()
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after - before < 1024  # 20,000 spans, nothing outlives a call


def test_engine_imports_no_jax():
    code = ("import sys, repro.core, repro.sql, repro.svc\n"
            "from repro.core import spans\n"
            "with spans.span('flint.task', job=1):\n"
            "    pass\n"
            "print(sorted(m for m in sys.modules if m.split('.')[0] "
            "== 'jax'))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=SRC))
    assert out.stdout.strip() == "[]"


def test_on_spans_nest_and_carry_their_ids(program_trace):
    def work():
        with spans.span("flint.job", job=7):
            with spans.span("flint.task", job=7, task=2, dispatch=11) as t:
                time.sleep(0.002)
                t.set_metadata(duplicates=1)

    _, events = program_trace(work)
    (job,) = [e for e in events if e[0] == "flint.job"]
    (task,) = [e for e in events if e[0] == "flint.task"]
    assert job[3] == task[3]  # one thread line
    assert job[1] <= task[1] < task[2] <= job[2]
    assert job[4] == {"job": 7}
    assert task[4] == {"job": 7, "task": 2, "dispatch": 11, "duplicates": 1}
    assert not spans.enabled()


def test_held_span_ends_where_another_starts(program_trace):
    def work():
        spans.hold("flint.shuffle.write")
        time.sleep(0.002)
        spans.hold("flint.shuffle.write")  # one held span at a time
        with spans.span("flint.scan"):
            time.sleep(0.002)
        spans.hold("flint.shuffle.write")
        with spans.keep_held(), spans.span("flint.shuffle.send"):
            time.sleep(0.002)
        spans.release()

    _, events = program_trace(work)
    writes = sorted(e for e in events if e[0] == "flint.shuffle.write")
    (scan,) = [e for e in events if e[0] == "flint.scan"]
    (send,) = [e for e in events if e[0] == "flint.shuffle.send"]
    assert len(writes) == 2
    assert writes[0][2] <= scan[1] and scan[2] <= writes[1][1]
    assert writes[1][1] <= send[1] < send[2] <= writes[1][2]


def test_each_thread_keeps_its_own_line(program_trace):
    import threading

    threads = 3
    # all alive at once, so that no thread reuses another's native id
    together = threading.Barrier(threads)

    def one(i):
        with spans.span("flint.task", task=i):
            spans.hold("flint.shuffle.write")
            time.sleep(0.002)
            spans.release()
            together.wait()

    def work():
        ts = [threading.Thread(target=one, args=(i,)) for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    _, events = program_trace(work)
    tasks = {e[4]["task"]: e for e in events if e[0] == "flint.task"}
    writes = [e for e in events if e[0] == "flint.shuffle.write"]
    assert sorted(tasks) == list(range(threads))
    assert len({e[3] for e in tasks.values()}) == threads
    assert len(writes) == threads
    for w in writes:
        (task,) = [t for t in tasks.values() if t[3] == w[3]]
        assert task[1] <= w[1] < w[2] <= task[2]
