"""The check's control and its planted faults, and the run that reads
both beside sound runs.

The configurations state exact integer sums. The device computes them
as f32 one-hot products at ``Precision.HIGHEST``, exact below 2**24. The
control is the plain grouped sum put in the kernel's place and computed
in the nearest precision below, bfloat16 operands with f32 accumulation:
the MXU's one-pass default, the step that would tempt a later change.
Values are rounded to bfloat16 with ``lax.reduce_precision``: a plain
cast to bfloat16 and back was compiled away on the chip, and its sums
came out exact.
The faults break the timed path where the answer is produced:

* ``half``: half of each batch left out, the sum scaled up from the rest;
* ``altered``: one group's sum off by one;
* ``unchanged``: sums left at their initial zeros.

On the chip, read a dozen sound seeds and three control seeds of a cell
in one process, each with a short window at the cell's own load:

    python3 bench/control.py --workload taxi-sqs.agg-hour \\
        --seeds 1,2,3 --control-seeds 4,5,6 --seconds 10

Each run prints one JSON line with the seed, the kind of run, ``correct``
and the compared numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("half", "altered", "unchanged")


@functools.cache
def _bf16_sums():
    import jax

    @functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
    def bf16_sums(vals, ids, n_buckets: int, interpret: bool):
        # reduce_precision rounds for certain: a bare f32 -> bf16 -> f32
        # convert pair may be elided by the TPU compiler (excess
        # precision), which leaves the sums exact
        rounded = jax.lax.reduce_precision(vals, exponent_bits=8,
                                           mantissa_bits=7)
        return jax.ops.segment_sum(rounded, ids, num_segments=n_buckets)
    return bf16_sums


def _faulty(kind: str, grouped_reduce):
    import numpy as np

    def fault(values, bucket_ids, n_buckets, **kw):
        vals = np.asarray(values, dtype=np.int64)
        ids = np.asarray(bucket_ids)
        if kind == "half" and len(vals) > 1:
            kept = grouped_reduce(vals[::2], ids[::2], n_buckets, **kw)
            scale = len(vals) / len(vals[::2])
            return np.rint(kept * scale).astype(np.int64)
        out = grouped_reduce(vals, ids, n_buckets, **kw)
        if kind == "altered" and len(out):
            out = out.copy()
            out[0] += 1
        elif kind == "unchanged":
            out = np.zeros_like(out)
        return out
    return fault


@contextlib.contextmanager
def planted(kind: str):
    """Run the block with the control (``bf16``) or a fault in the
    program's grouped sum."""
    from repro.kernels import ops

    name = "_kernel_sums" if kind == "bf16" else "grouped_reduce"
    saved = getattr(ops, name)
    if kind == "bf16":
        setattr(ops, name, _bf16_sums())
    elif kind in FAULTS:
        setattr(ops, name, _faulty(kind, saved))
    else:
        raise ValueError(f"unknown control or fault {kind!r}")
    try:
        yield
    finally:
        setattr(ops, name, saved)


def read(workload: str, seed: int, seconds: float, kind: str | None,
         **kw) -> dict:
    """One run of ``workload`` (sound where ``kind`` is None); returns
    its seed, kind, ``correct``, counts and compared numbers."""
    from bench import harness

    with planted(kind) if kind else contextlib.nullcontext():
        r = harness.run_cell(workload, seed, seconds, False,
                             t_process=time.monotonic(), **kw)
    return {"seed": seed, "kind": kind or "sound", "correct": r["correct"],
            "attempted": r["attempted"], "failed": r["failed"],
            "checks": {k: v["value"] for k, v in r["checks"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    runs = ([(int(s), None) for s in args.seeds.split(",") if s]
            + [(int(s), "bf16") for s in args.control_seeds.split(",") if s])
    for seed, kind in runs:
        print(json.dumps(read(args.workload, seed, args.seconds, kind)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
