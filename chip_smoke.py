"""Smoke test of the Flint SQL path on one TPU chip.

Generates the seeded synthetic taxi CSV, uploads it to a FlintContext and
runs the two SQL taxi queries of ``benchmarks/shuffle_backends.py``
(``sql_filter_groupby``, ``sql_join_agg``) with ``vector_backend="jax"``,
which sends the integer group sums to the compiled ``bucket_reduce`` Pallas
kernel. Each answer must equal, exactly, the same query on the numpy
backend and on the row path (``vectorize=False``, the plain reference).
Before that, one phase checks the kernel's exactness on the chip with
per-chunk sums just under its 2**24 envelope.

Earlier lines report the device, the row count, wall seconds per phase
(generation and first query runs are set-up, compiles included; none of
them is a speed measurement), the device counters and the number of
distinct programs compiled. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Exits non-zero, with no result line, when JAX finds no TPU or any check
fails. Everything runs in this one process, which holds the chip.

    python chip_smoke.py [--rows N] [--seed S]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    pass


def _log(msg: str) -> None:
    print(msg, flush=True)


def _typed(rows) -> list:
    """Rows sorted, with each value's concrete type: equal answers must
    agree on int vs float, not just on ==."""
    return sorted(tuple((type(v).__name__, v) for v in r) for r in rows)


def check_kernel_exactness(seed: int) -> None:
    """Per-chunk sums just under 2**24 with values that need all 24 bits
    of an f32 mantissa: any bf16 rounding in the MXU passes shows here.
    Two more cases check the limb sums past that envelope and the x64
    path past the kernel's groups."""
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    cases = []
    # 24 groups; one odd value near 2**23 tops the chunk up to 2**24 - 1
    vals = rng.integers(-(2**11), 2**11, 8191)
    rest = (2**24 - 1) - int(np.abs(vals).sum())
    cases.append((np.append(vals, rest), rng.integers(0, 24, 8192), 24))
    # one group whose running partial climbs to 2**24 - 1
    one = rng.integers(1, 2**12, 5000)
    one[-1] += (2**24 - 1) - int(one.sum())
    cases.append((one, np.zeros(5000, dtype=np.int64), 1))
    # past 2**24: 11-bit limbs of both signs on the same kernel
    big = rng.integers(-(2**40), 2**40, 8192)
    cases.append((big, rng.integers(0, 24, 8192), 24))
    # past the kernel's groups: the x64 segment sum (int64 on the chip)
    cases.append((big, np.arange(8192) % 8200, 8200))
    for vals, ids, groups in cases:
        path = "kernel_calls" if groups <= 8192 else "x64_sums"
        stats: dict = {}
        got = ops.grouped_reduce(vals, ids, groups, stats=stats)
        want = np.zeros(groups, dtype=np.int64)
        np.add.at(want, ids, vals)
        if stats.get(path) != 1 or "device_fallbacks" in stats:
            raise SmokeFailure(f"exactness case took {stats}, not {path}")
        if not np.array_equal(got, want):
            bad = np.flatnonzero(got != want)
            raise SmokeFailure(
                f"kernel sums inexact near 2**24 in groups {bad[:5]}: "
                f"got {got[bad[:5]]}, want {want[bad[:5]]}")


def run_checks(rows: int, seed: int) -> None:
    """Every phase except the device check; raises SmokeFailure on a wrong
    answer or a missing kernel call."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import ops

    ops.use_compile_cache()
    _log(f"compile cache: {ops.compile_cache_dir()}")

    t0 = time.monotonic()
    check_kernel_exactness(seed)
    _log(f"phase kernel_exactness: ok, {time.monotonic() - t0:.3f} s "
         f"(set-up, compiles included)")

    from benchmarks.shuffle_backends import SQL_WORKLOADS
    from repro.core import FlintConfig, FlintContext
    from repro.data.synthetic import taxi_csv

    t0 = time.monotonic()
    data = taxi_csv(rows, seed=seed)
    _log(f"phase generate: {rows} rows, {len(data)} bytes, "
         f"{time.monotonic() - t0:.3f} s")

    # a 900 s lease (Lambda's maximum) and a 600 s drain allowance: at
    # this scale a map task can run for minutes before its only flush,
    # and the default 30 s drain inactivity timeout would retry consumers
    base = dict(concurrency=16, time_limit_s=900.0, drain_timeout_s=600.0)
    legs = [("jax", dict(vector_backend="jax")),
            ("numpy", dict(vector_backend="numpy")),
            ("row", dict(vectorize=False))]
    report = {}
    for name, query in SQL_WORKLOADS.items():
        answers = {}
        for leg, kw in legs:
            runs = 2 if leg == "jax" else 1
            for i in range(runs):
                ctx = FlintContext(config=FlintConfig(**base, **kw))
                ctx.upload("taxi.csv", data)
                t0 = time.monotonic()
                out = query(ctx)
                wall = time.monotonic() - t0
                dev = ctx.last_scheduler.device_stats
                label = "first run, set-up" if i == 0 else "later run"
                _log(f"phase {name}/{leg} ({label}): {wall:.3f} s, "
                     f"{len(out)} result rows, device {dev}")
                answers.setdefault(leg, _typed(out))
                if _typed(out) != answers[leg]:
                    raise SmokeFailure(f"{name}/{leg}: runs disagree")
                if leg == "jax":
                    if dev["kernel_calls"] == 0:
                        raise SmokeFailure(f"{name}: no kernel call")
                    if dev["device_fallbacks"]:
                        raise SmokeFailure(f"{name}: device fallbacks {dev}")
                    report[name] = dev
        for leg in ("numpy", "row"):
            if answers[leg] != answers["jax"]:
                raise SmokeFailure(f"{name}: jax answer != {leg} answer")
        _log(f"check {name}: jax == numpy == row "
             f"({len(answers['jax'])} rows)")
    programs = ops.compiled_programs()
    _log(f"compiled grouped_reduce programs: {programs}; kernel calls "
         f"{sum(d['kernel_calls'] for d in report.values())}")
    if programs["kernel"] == 0:
        raise SmokeFailure("the kernel never compiled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2_000_000)
    ap.add_argument("--seed", type=int, default=13)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    _log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
         f"devices {len(devices)}, backend {jax.default_backend()}")
    if dev.platform != "tpu" or jax.default_backend() != "tpu":
        # without a TPU the kernel would only run interpreted
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r})",
              file=sys.stderr)
        return 2
    try:
        run_checks(args.rows, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
