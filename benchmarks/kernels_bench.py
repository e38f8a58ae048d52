"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute in interpret mode, so
wall-clock here measures the REFERENCE implementations (the jnp oracles,
which XLA compiles natively) — a correctness-bench, plus arithmetic
intensity derived per shape so the TPU roofline slot of each kernel is
visible without hardware.

``--quick`` is the CI smoke leg: tiny shapes, every Pallas kernel run in
interpret mode and asserted against its oracle, plus the exactness
envelopes of ``ops.grouped_reduce`` — the grouped-aggregation backend of
the vectorized SQL engine (docs/vectorized_execution.md), which makes
this path load-bearing for query results, not just for model code.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import ref

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / iters


def flash_rows():
    rows = []
    key = jax.random.PRNGKey(0)
    for (b, h, kk, s, d) in [(1, 8, 2, 1024, 128), (1, 8, 8, 2048, 64)]:
        q = jax.random.normal(key, (b, s, h, d), jnp.float32)
        k = jax.random.normal(key, (b, s, kk, d), jnp.float32)
        v = jax.random.normal(key, (b, s, kk, d), jnp.float32)
        fn = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
        dt = _time(fn, q, k, v)
        flops = 4.0 * b * h * s * s * d  # qk + pv
        bytes_ = (q.size + k.size + v.size + q.size) * 4
        rows.append({
            "name": f"flash_ref_b{b}h{h}s{s}d{d}",
            "us_per_call": dt * 1e6,
            "derived": f"AI={flops/bytes_:.0f}flop/B "
                       f"tpu_pred={max(flops/PEAK_FLOPS, bytes_/HBM_BW)*1e6:.1f}us",
        })
    return rows


def bucket_rows():
    rows = []
    key = jax.random.PRNGKey(1)
    for (n, p, d) in [(65536, 160, 512), (16384, 16, 1024)]:
        vals = jax.random.normal(key, (n, d), jnp.float32)
        ids = jax.random.randint(key, (n,), 0, p)
        fn = jax.jit(lambda v, i: ref.bucket_reduce_ref(v, i, p))
        dt = _time(fn, vals, ids)
        flops = 2.0 * n * p * d
        rows.append({
            "name": f"bucket_reduce_ref_n{n}p{p}d{d}",
            "us_per_call": dt * 1e6,
            "derived": f"tpu_pred={flops/PEAK_FLOPS*1e6:.1f}us",
        })
    return rows


def gmm_rows():
    rows = []
    key = jax.random.PRNGKey(2)
    for (e, t, d, f) in [(8, 1024, 512, 2048), (160, 128, 512, 1536)]:
        x = jax.random.normal(key, (e, t, d), jnp.float32)
        w = jax.random.normal(key, (e, d, f), jnp.float32)
        fn = jax.jit(ref.grouped_matmul_ref)
        dt = _time(fn, x, w)
        flops = 2.0 * e * t * d * f
        rows.append({
            "name": f"gmm_ref_e{e}t{t}d{d}f{f}",
            "us_per_call": dt * 1e6,
            "derived": f"tpu_pred={flops/PEAK_FLOPS*1e6:.1f}us",
        })
    return rows


def quick_rows():
    """CI smoke: interpret-mode kernels vs their oracles on tiny shapes,
    and the grouped_reduce exactness envelopes — hard assertions, a
    correctness gate rather than a timing run."""
    import numpy as np

    from repro.kernels import ops

    rows = []
    key = jax.random.PRNGKey(3)

    def timed(name, fn):
        t0 = time.monotonic()
        fn()
        rows.append({"name": name,
                     "us_per_call": (time.monotonic() - t0) * 1e6,
                     "derived": "smoke"})

    def check_flash():
        q = jax.random.normal(key, (1, 64, 2, 16), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 64, 2, 16),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 2, 16),
                              jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        assert jnp.allclose(got, want, atol=1e-4), "flash kernel != oracle"

    def check_bucket():
        vals = jax.random.normal(key, (256, 8), jnp.float32)
        ids = jax.random.randint(key, (256,), 0, 16)
        got = ops.bucket_reduce(vals, ids.astype(jnp.int32), 16,
                                interpret=True)
        want = ref.bucket_reduce_ref(vals, ids, 16)
        assert jnp.allclose(got, want, atol=1e-4), \
            "bucket_reduce kernel != oracle"

    def check_gmm():
        x = jax.random.normal(key, (2, 16, 16), jnp.float32)
        w = jax.random.normal(key, (2, 16, 16), jnp.float32)
        got = ops.grouped_matmul(x, w, interpret=True)
        want = ref.grouped_matmul_ref(x, w)
        assert jnp.allclose(got, want, atol=1e-4), "gmm kernel != oracle"

    def check_grouped_reduce():
        rng = np.random.default_rng(7)
        ids = rng.integers(0, 16, size=500)

        def fold(vals):
            acc = np.zeros(16, dtype=object)
            for i, v in zip(ids, vals):
                acc[i] += int(v)
            return acc

        # envelope 1: sum(|v|) < 2**24 — the one-hot-matmul kernel
        small = rng.integers(-50, 50, size=500)
        got = ops.grouped_reduce(small, ids, 16, interpret=True)
        assert got.dtype == np.int64 and (got == fold(small)).all(), \
            "grouped_reduce kernel envelope != bigint fold"
        # past 2**24: the same kernel, summing limb columns
        big = rng.integers(-2**40, 2**40, size=500)
        got = ops.grouped_reduce(big, ids, 16, interpret=True)
        assert (got == fold(big)).all(), \
            "grouped_reduce limb sums != bigint fold"
        # past the envelope: refuse (caller keeps its exact path)
        over = np.array([2**62, 2**62], dtype=np.int64)
        assert ops.grouped_reduce(over, np.array([0, 0]), 1,
                                  interpret=True) is None
        # empty input: zeros, no kernel launch
        empty = ops.grouped_reduce(np.array([], dtype=np.int64),
                                   np.array([], dtype=np.int64), 4,
                                   interpret=True)
        assert (empty == np.zeros(4, dtype=np.int64)).all()

    timed("flash_attention_smoke", check_flash)
    timed("bucket_reduce_smoke", check_bucket)
    timed("grouped_matmul_smoke", check_gmm)
    timed("grouped_reduce_smoke", check_grouped_reduce)
    return rows


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--quick" in argv:
        rows = quick_rows()
    else:
        rows = flash_rows() + bucket_rows() + gmm_rows()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if "--quick" in argv:
        print("# kernel smoke passed")
    return rows


if __name__ == "__main__":
    main()
