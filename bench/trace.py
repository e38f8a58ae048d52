"""Reduce a profiler trace of one run's window to the benchmark's numbers.

The profiler writes an ``.xplane.pb``; ``load`` turns it into plain
tuples and ``reduce_trace`` does the arithmetic, so that the arithmetic is
tested on a synthetic trace with a known answer:

* busy: the union of the intervals in which an operation ran on each
  device (the ``XLA Ops`` line; the ``XLA Modules`` line where a device
  has no op line), clipped to the window and averaged over the devices;
* grouped-sum time: the summed device durations of the two programs of
  the program's grouped sum, the jitted ``_kernel_sums`` and
  ``_x64_sums``, found by name on the module line;
* idle gaps: the holes between busy intervals inside the window, each
  named by the innermost of the benchmark's own host spans
  (``bench.query.<name>``, ``bench.between_queries``, ...) that covers
  its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
#: the jit names of the grouped sum's device programs (kernels/ops.py)
GROUPED_SUM_PROGRAMS = ("_kernel_sums", "_x64_sums")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Trace:
    """``devices``: plane name -> line name -> [(event, start_ns, end_ns)];
    ``spans``: the benchmark's host spans as (name, start_ns, end_ns)."""
    devices: dict
    spans: list


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(hlo: str) -> str:
    """``%copy = f32[8192,1]{1,0:T(8,128)} copy(...)`` -> ``%copy
    f32[8192,1]``: the op and its shape, without layout or operands."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{name} {shape}" if shape else name


def _label(spans, t) -> str:
    """Innermost benchmark span (latest start) covering time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and (best is None
                                                     or s > best[1]):
            best = (name, s)
    return best[0] if best else WINDOW_SPAN


def reduce_trace(trace: Trace) -> dict | None:
    """The window's device numbers in seconds, or None where the trace
    holds no device plane or no window span."""
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not trace.devices or len(windows) != 1:
        return None
    lo, hi = windows[0]
    busy_s, gsum_s, gsum_calls = [], 0.0, 0
    op_time: dict = {}
    gaps = []
    for i, plane in enumerate(sorted(trace.devices)):
        lines = trace.devices[plane]
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        busy = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for name, s, e in lines.get(OP_LINE, ()):
            if lo <= s < hi:
                key = op_name(name)
                op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        for name, s, e in lines.get(MODULE_LINE, ()):
            if lo <= s < hi and any(p in name for p in GROUPED_SUM_PROGRAMS):
                gsum_s += (e - s) / 1e9
                gsum_calls += 1
        if i == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "devices": len(busy_s),
        "grouped_sum_s": gsum_s,
        "grouped_sum_programs": gsum_calls,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [(_label(trace.spans, (s + e) / 2), (e - s) / 1e9)
                      for s, e in gaps[:TOP]],
    }
