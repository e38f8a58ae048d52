"""jit'd public wrappers for the Pallas kernels.

Backend policy: on TPU the Mosaic kernels run compiled; on the CPU
backend `interpret=True` executes the kernel bodies for correctness, and
the pure-jnp refs remain the oracles. Any other backend gets the compiled
path and fails there loudly. Tests sweep shapes/dtypes against
repro.kernels.ref.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import numpy as np

from repro.core.spans import span
from repro.kernels import ref
from repro.kernels.bucket_reduce import bucket_reduce as _bucket_reduce
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gmm import grouped_matmul as _gmm

#: the checkout root (src/repro/kernels/ops.py -> three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def _interpret() -> bool:
    """Interpret Pallas kernels only on the CPU backend."""
    return jax.default_backend() == "cpu"


def compile_cache_dir() -> str:
    """Where compiled device programs persist across processes:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else one fixed directory
    in the checkout (the path is part of the cache key, so it never
    derives from a temp name, a pid or the time)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_CHECKOUT / ".jax_cache"))


@functools.cache
def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache, once per process. Mosaic
    kernel compiles take about a second, below JAX's default floor for
    caching, so the floor goes to zero."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@functools.lru_cache(maxsize=None)
def _fa_with_vjp(causal: bool, window: int, interpret: bool):
    """pallas_call is not reverse-differentiable; forward runs the kernel,
    backward recomputes attention with the jnp reference (the train path
    uses the chunked pure-JAX attention anyway — the kernel serves the
    prefill/serving plane)."""

    def fwd_impl(q, k, v):
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        sq, skv = qt.shape[2], kt.shape[2]
        bq = 128 if sq % 128 == 0 else _largest_block(sq)
        bk = 128 if skv % 128 == 0 else _largest_block(skv)
        out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   bq=bq, bk=bk, interpret=interpret)
        return out.transpose(0, 2, 1, 3)

    @jax.custom_vjp
    def fa(q, k, v):
        return fwd_impl(q, k, v)

    def fwd(q, k, v):
        return fwd_impl(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal,
                                                    window=window), q, k, v)
        return vjp(g.astype(q.dtype))

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool | None = None):
    """q: (B, S, H, D) k/v: (B, S, K, D) — model layout; kernel runs BHSD."""
    interpret = _interpret() if interpret is None else interpret
    return _fa_with_vjp(causal, int(window), interpret)(q, k, v)


def _largest_block(n: int, cap: int = 128) -> int:
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


def bucket_reduce(values, bucket_ids, n_buckets: int, *,
                  interpret: bool | None = None):
    interpret = _interpret() if interpret is None else interpret
    return _bucket_reduce(values, bucket_ids, n_buckets, interpret=interpret)


#: exactness envelopes of grouped_reduce on sum(|v|) of a chunk
_KERNEL_EXACT = 2**24  # every f32 partial is an exact integer below this
_X64_EXACT = 2**62  # an int64 accumulator cannot wrap at or below this
#: bounded shapes: rows pad to a power of two >= one kernel block, groups
#: to a power of two >= one lane tile, so a query compiles a handful of
#: programs (log2 of the row and group ranges), not one per chunk
_MIN_ROWS = 1024
_MIN_GROUPS = 128
#: the one-hot kernel is compiled and rehearsed up to this many groups;
#: wider chunks take the x64 segment sum, which is exact there too
_MAX_KERNEL_GROUPS = 8192


def _pow2_at_least(n: int, floor: int) -> int:
    return max(floor, 1 << (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def _kernel_sums(vals, ids, n_buckets: int, interpret: bool):
    return _bucket_reduce(vals[:, None], ids, n_buckets,
                          interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("n_buckets",))
def _x64_sums(vals, ids, n_buckets: int):
    return jax.ops.segment_sum(vals, ids, num_segments=n_buckets)


def compiled_programs() -> dict:
    """Distinct programs compiled so far for grouped_reduce, per path."""
    return {"kernel": _kernel_sums._cache_size(),
            "x64": _x64_sums._cache_size()}


def _count(stats: dict | None, name: str, k: int = 1) -> None:
    if stats is not None:
        stats[name] = stats.get(name, 0) + k


def grouped_reduce(values, bucket_ids, n_buckets: int, *,
                   interpret: bool | None = None,
                   stats: dict | None = None):
    """int64 grouped sum for the vectorized SQL engine
    (vector_backend="jax"). Integer addition is associative, so an
    order-free reduction is EXACT as long as nothing can overflow:

      * sum(|v|) < 2**24  — every value and every partial is an exact
        f32 integer, so the bucket_reduce one-hot-matmul kernel (full-f32
        MXU accumulation) gives bit-exact results;
      * sum(|v|) <= 2**62 — an x64 segment sum accumulates in int64
        with no possible wrap;
      * otherwise returns None and the caller keeps its exact path
        (the numpy engine falls back to Python bigint folds).

    Device errors propagate. ``stats``, when given, counts the path taken
    (``kernel_calls`` / ``x64_sums`` / ``device_fallbacks``), and the rows
    a device program summed before and after padding (``device_rows`` /
    ``device_padded_rows``). The call runs under a ``flint.grouped_sum``
    span, its blocking fetch of the sums under ``flint.grouped_sum.fetch``.
    Returns a (n_buckets,) numpy int64 array, or None."""
    with span("flint.grouped_sum", rows=len(values), groups=n_buckets):
        vals = np.asarray(values, dtype=np.int64)
        n = vals.shape[0]
        if n == 0:
            return np.zeros(n_buckets, dtype=np.int64)
        # abs in float64: np.abs of int64 min wraps to a negative value
        abs_sum = float(np.abs(vals.astype(np.float64)).sum())
        if abs_sum > _X64_EXACT:
            _count(stats, "device_fallbacks")
            return None
        interpret = _interpret() if interpret is None else interpret
        if not interpret:
            use_compile_cache()
        rows = _pow2_at_least(n, _MIN_ROWS)
        groups = _pow2_at_least(n_buckets, _MIN_GROUPS)
        # pad rows carry value 0 and id -1: they land in no bucket
        ids = np.full(rows, -1, dtype=np.int32)
        ids[:n] = np.asarray(bucket_ids)
        if abs_sum < _KERNEL_EXACT and groups <= _MAX_KERNEL_GROUPS:
            padded = np.zeros(rows, dtype=np.float32)
            padded[:n] = vals
            out = _kernel_sums(padded, ids, groups, interpret)
            _count(stats, "kernel_calls")
        else:
            padded = np.zeros(rows, dtype=np.int64)
            padded[:n] = vals
            with jax.enable_x64(True):
                out = _x64_sums(padded, ids, groups)
            _count(stats, "x64_sums")
        _count(stats, "device_rows", n)
        _count(stats, "device_padded_rows", rows)
        with span("flint.grouped_sum.fetch"):
            out = np.asarray(out, dtype=np.int64)
        return out[:n_buckets]


def grouped_matmul(x, w, sizes=None, *, interpret: bool | None = None):
    """x: (E, T, D) @ w: (E, D, F). `sizes` accepted for API compatibility
    (rows past a group's size are zero in the dispatch buffers)."""
    del sizes
    interpret = _interpret() if interpret is None else interpret
    e, t, d = x.shape
    f = w.shape[2]
    if t % 8 or d % 8 or f % 8:  # tiny/test shapes: use the oracle
        return ref.grouped_matmul_ref(x, w)
    bt = _largest_block(t)
    bf = _largest_block(f)
    bd = _largest_block(d, 512)
    return _gmm(x, w, bt=bt, bf=bf, bd=bd, interpret=interpret)
