"""The program's own spans in a profiled window, and a traced run of a
cell with them switched on.

Once tracing is switched on (``repro.core.spans.enable``), the engine
opens ``flint.*`` spans at its layer boundaries. They lie on the
profiler's host plane, one line per thread, on the clock of the device
planes that ``bench.trace`` reads. ``load`` reads them beside what
``bench.trace.load`` reads, and ``reduce`` gives, of the program spans
that start inside the window:

* each name's total and self thread-seconds (self: minus the time in
  spans nested in it on the same thread line);
* the queue wait of tasks: from the end of each ``flint.dispatch`` to
  the start of the ``flint.task`` carrying its ``dispatch`` id;
* the ``duplicates`` that ``flint.shuffle.drain`` spans record;
* unattributed time: the self time of ``flint.task``, plus the time in
  ``bench.query.*`` on its thread line outside every program span;
* idle time by span: each idle gap's time split evenly over the
  innermost program spans open across threads during it;
* the idle gaps as ``bench.trace`` labels them, with ``/`` and the
  program span that has the most thread-time in the gap appended where
  one is open there;
* how many grouped-sum device programs lie inside a ``flint.grouped_sum``
  host span: the check that both are on one clock.

Run as a script, it makes one traced run of a cell through the harness's
own ``run_cell``, its profiled block swapped for ``profiled`` here, and
prints the result line with these numbers added under ``program_spans``
and the relabelled gaps in ``breakdown``:

    python3 bench/program_spans.py --workload taxi-sqs.agg-hour \\
        --seed 7 --seconds 51
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
import glob
import os
import sys
import tempfile
import time

T_PROCESS = time.monotonic()

import numpy as np  # noqa: E402

if __package__ in (None, ""):  # run as a script
    sys.path[:0] = [os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))]
from bench import trace as tr  # noqa: E402

PREFIX = "flint."
QUERY_PREFIX = "bench.query."
#: thread-milliseconds per query: name -> (spans counted whole, spans
#: counted by their self time)
SPAN_MS = {
    "plan.ms_per_query": ((), ("flint.plan",)),
    "scheduler.dispatch_ms_per_query": (("flint.dispatch",), ()),
    "ingest.ms_per_query": ((), ("flint.scan", "flint.ingest")),
    "fused_op.host_ms_per_query": ((), ("flint.fused",)),
    "grouped_sum.host_ms_per_query": (("flint.grouped_sum",), ()),
    "shuffle.write_ms_per_query": (("flint.shuffle.write",), ()),
    "shuffle.read_ms_per_query": ((), ("flint.shuffle.drain",)),
    "shuffle.wait_ms_per_query": (("flint.shuffle.wait",), ()),
    "shuffle.fold_ms_per_query": (("flint.shuffle.fold",), ()),
    "driver.finish_ms_per_query": (("flint.merge", "flint.teardown"), ()),
}


@dataclasses.dataclass
class Spans:
    """``trace``: what ``bench.trace.load`` reads; ``program``: the
    program's spans as (name, start_ns, end_ns, thread line, stats dict);
    ``lines``: the thread line of each of ``trace.spans``."""
    trace: tr.Trace
    program: list = dataclasses.field(default_factory=list)
    lines: list = dataclasses.field(default_factory=list)


def load(log_dir: str) -> Spans:
    """Read the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    trace = tr.load(log_dir)
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    program, lines = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            key = f"{plane.name}#{i}"
            for e in line.events:
                if e.name.startswith(tr.SPAN_PREFIX):
                    lines.append(key)
                elif e.name.startswith(PREFIX):
                    program.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, key,
                                    dict(e.stats)))
    return Spans(trace, program, lines)


def reduce(spans: Spans) -> dict | None:
    """The reductions of the module docstring, in seconds, or None where
    the trace holds no device plane or no window span."""
    trace = spans.trace
    windows = [(s, e) for n, s, e in trace.spans if n == tr.WINDOW_SPAN]
    if not trace.devices or len(windows) != 1:
        return None
    lo, hi = windows[0]
    planes = sorted(trace.devices)
    first = trace.devices[planes[0]]
    ops = first.get(tr.OP_LINE) or first.get(tr.MODULE_LINE) or []
    busy = tr.union(tr._clip([(s, e) for _, s, e in ops], lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]
    gsum_events = [(s, e) for p in planes
                   for name, s, e in trace.devices[p].get(tr.MODULE_LINE, ())
                   if lo <= s < hi
                   and any(g in name for g in tr.GROUPED_SUM_PROGRAMS)]

    by_line: dict = {}
    for sp in spans.program:
        by_line.setdefault(sp[3], []).append(sp)
    inside = [sp for sp in spans.program if lo <= sp[1] < hi]
    total: dict = {}
    own: dict = {}
    stretches = []
    for sps in by_line.values():
        for s, e, i in _innermost(sps):
            name, start = sps[i][0], sps[i][1]
            stretches.append((s, e, name))
            if lo <= start < hi:
                own[name] = own.get(name, 0.0) + (e - s) / 1e9
    for name, s, e, _, _ in inside:
        total[name] = total.get(name, 0.0) + (e - s) / 1e9
    sent = {st["dispatch"]: e for name, s, e, _, st in spans.program
            if name == "flint.dispatch" and "dispatch" in st}
    queue = sum(max(0.0, s - sent[st["dispatch"]]) for name, s, e, _, st
                in inside if name == "flint.task"
                and st.get("dispatch") in sent) / 1e9
    client = 0.0
    for (name, s, e), line in zip(trace.spans, spans.lines):
        if name.startswith(QUERY_PREFIX) and lo <= s < hi:
            on_line = [(ps, pe) for _, ps, pe, *_ in by_line.get(line, ())]
            client += ((e - s) - _covered(on_line, s, e)) / 1e9
    busiest = functools.partial(_busiest, stretches, _as_array(stretches))
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:tr.TOP]
    return {
        "program_spans": len(inside),
        "span_total_s": total,
        "span_self_s": own,
        "queue_wait_s": queue,
        "duplicates": sum(st.get("duplicates", 0) for name, _, _, _, st
                          in inside if name == "flint.shuffle.drain"),
        "unattributed_s": own.get("flint.task", 0.0) + client,
        "idle_gaps": [(tr._label(trace.spans, (s + e) / 2) + busiest(s, e),
                       (e - s) / 1e9) for s, e in longest],
        "idle_by_span": _split(stretches, gaps)[:tr.TOP],
        "grouped_sum_programs": len(gsum_events),
        "grouped_sum_programs_in_span": _within(
            gsum_events, [(s, e) for name, s, e, _, _ in spans.program
                          if name == "flint.grouped_sum"]),
    }


def per_query(r: dict | None, queries: int) -> dict:
    """The per-query numbers of ``reduce``'s result ``r``: thread-ms of
    each layer (``SPAN_MS``), the queue wait, the unattributed time, and
    duplicates; empty where the trace holds no program span."""
    if not r or not r["program_spans"] or not queries:
        return {}
    out = {name: (sum(r["span_total_s"].get(n, 0.0) for n in whole)
                  + sum(r["span_self_s"].get(n, 0.0) for n in own))
           * 1e3 / queries for name, (whole, own) in SPAN_MS.items()}
    out["scheduler.queue_ms_per_query"] = r["queue_wait_s"] * 1e3 / queries
    out["host.unattributed_ms_per_query"] = (r["unattributed_s"] * 1e3
                                             / queries)
    out["shuffle.duplicates_per_query"] = r["duplicates"] / queries
    return out


def _innermost(spans) -> list:
    """(start, end, index) stretches of one thread line in which
    ``spans[index]`` is the innermost open span."""
    events = []
    for i, sp in enumerate(spans):
        if sp[2] > sp[1]:
            events.append((sp[1], 1, -sp[2], i))
            events.append((sp[2], 0, 0, i))
    events.sort()
    out, stack, t_prev = [], [], 0
    for t, opening, _, i in events:
        if stack and t > t_prev:
            out.append((t_prev, t, stack[-1]))
        if opening:
            stack.append(i)
        else:
            stack.remove(i)
        t_prev = t
    return out


def _covered(intervals, lo, hi) -> float:
    return sum(e - s for s, e in tr.union(tr._clip(intervals, lo, hi)))


def _split(stretches, gaps) -> list:
    """Each gap's time split evenly over the stretches open during it:
    [(name, seconds)], most first."""
    if not gaps or not stretches:
        return []
    starts = [s for s, _ in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + (e - s))

    def gap_time_before(t):
        j = bisect.bisect_right(starts, t) - 1
        if j < 0:
            return 0.0
        s, e = gaps[j]
        return cum[j] + max(0.0, min(t, e) - s)

    events = sorted([(s, 1, n) for s, e, n in stretches]
                    + [(e, -1, n) for s, e, n in stretches])
    active: dict = {}
    k = 0
    out: dict = {}
    g_prev = gap_time_before(events[0][0])
    for t, delta, name in events:
        g = gap_time_before(t)
        if k and g > g_prev:
            share = (g - g_prev) / k
            for n, c in active.items():
                out[n] = out.get(n, 0.0) + share * c / 1e9
        g_prev = g
        active[name] = active.get(name, 0) + delta
        if not active[name]:
            del active[name]
        k += delta
    return sorted(out.items(), key=lambda kv: -kv[1])


def _as_array(stretches):
    return np.asarray([(s, e) for s, e, _ in stretches] or np.zeros((0, 2)),
                      dtype=np.float64)


def _busiest(stretches, arr, lo, hi) -> str:
    """``/`` and the program span with the most thread-time in [lo, hi],
    or nothing where none is open there."""
    over = np.clip(np.minimum(arr[:, 1], hi) - np.maximum(arr[:, 0], lo),
                   0.0, None)
    time_in: dict = {}
    for j in np.flatnonzero(over):
        name = stretches[j][2]
        time_in[name] = time_in.get(name, 0.0) + over[j]
    if not time_in:
        return ""
    return "/" + max(time_in.items(), key=lambda kv: kv[1])[0]


def _within(events, spans) -> int:
    """How many of the (start, end) ``events`` lie inside some span."""
    if not events or not spans:
        return 0
    spans = sorted(spans)
    starts = np.asarray([s for s, _ in spans], dtype=np.float64)
    reach = np.maximum.accumulate(np.asarray([e for _, e in spans],
                                             dtype=np.float64))
    j = np.searchsorted(starts, [s for s, _ in events], side="right") - 1
    ends = np.asarray([e for _, e in events], dtype=np.float64)
    return int(np.sum((j >= 0) & (reach[np.maximum(j, 0)] >= ends)))


@contextlib.contextmanager
def profiled(enabled: bool, keep: dict | None = None):
    """The harness's profiled block with the program's spans switched on
    inside it. ``keep`` gets ``spans`` (``load``'s result) and
    ``program`` (``reduce``'s)."""
    holder: dict = {"trace": None}
    if not enabled:
        yield holder
        return
    import jax

    from repro.core import spans

    keep = {} if keep is None else keep
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        spans.enable(True)
        try:
            yield holder
        finally:
            spans.enable(False)
            jax.profiler.stop_trace()
        keep["spans"] = load(d)
        holder["trace"] = tr.reduce_trace(keep["spans"].trace)
        keep["program"] = reduce(keep["spans"])


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    sys.path[:0] = [os.path.join(root, "src")]
    from bench import harness

    keep: dict = {}
    harness.profiled = functools.partial(profiled, keep=keep)
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, t_process=T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    r = keep.get("program")
    if r:
        queries = result["attempted"] - result["failed"]
        result["program_spans"] = {
            **per_query(r, queries),
            "grouped_sum_programs": r["grouped_sum_programs"],
            "grouped_sum_programs_in_span": r["grouped_sum_programs_in_span"],
            "spans": r["program_spans"]}
        result["breakdown"]["idle_gaps"] = [list(g) for g in r["idle_gaps"]]
        result["breakdown"]["idle_by_span"] = [list(g)
                                               for g in r["idle_by_span"]]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
