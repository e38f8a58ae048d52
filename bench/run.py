"""Run one benchmark cell once on this machine's chips.

    python3 bench/run.py --workload taxi-sqs.agg-hour --seed 7 \\
        --seconds 51 --trace 0

Generates the cell's data from ``--seed``, builds the deployment, runs
each query of the cell's traffic once to warm its shapes (set-up), then
drives the traffic for ``--seconds`` and checks every answer against the
plain reference (``bench/reference.py``). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiled window), ``device``, with ``--trace 1``
a ``breakdown``, and last the compared numbers with their limits under
``checks``, which also end standard error.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell needs. Compiled programs persist in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives in the checkout, whatever the machine sets
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
