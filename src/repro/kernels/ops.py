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


#: exactness envelopes of grouped_reduce on sum(|v|) of a chunk's column
_KERNEL_EXACT = 2**24  # every f32 partial is an exact integer below this
_X64_EXACT = 2**62  # an int64 accumulator cannot wrap at or below this
#: bounded shapes: rows pad to a power of two >= one kernel block, groups
#: to a power of two >= one lane tile, limb columns to a power of two, so
#: a query compiles a handful of programs, not one per chunk
_MIN_ROWS = 1024
_MIN_GROUPS = 128
#: the one-hot kernel is compiled and rehearsed up to this many groups
#: and limb columns; wider chunks take the x64 segment sum, which is
#: exact there too
_MAX_KERNEL_GROUPS = 8192
_MAX_LIMBS = 32


def _pow2_at_least(n: int, floor: int) -> int:
    return max(floor, 1 << (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def _kernel_sums(vals, ids, n_buckets: int, interpret: bool):
    """(rows,) or (rows, D) f32 limb columns -> (n_buckets, D) sums."""
    return _bucket_reduce(vals.reshape(vals.shape[0], -1), ids, n_buckets,
                          interpret=interpret)


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


def limb_width(n: int) -> int:
    """The most bits a limb may hold so that ``n`` rows of limbs sum
    below 2**24: n * (2**w - 1) < 2**24 (11 bits at 8,192 rows)."""
    w = 0
    while n * ((2 << w) - 1) < _KERNEL_EXACT:
        w += 1
    return w


def _limbs(col, abs_sum: float, width: int) -> list:
    """``col`` (int64, sum(|v|) <= 2**62) as limb columns: (sign, shift,
    limb) with col == sum(sign * (limb << shift)), and every limb
    column's sum of |v| below 2**24. A column whose sum of |v| is below
    2**24 is its own limb; a wider one is split into its positive values
    and its negated negative values, and a part whose sum is past 2**24
    is cut into ``width``-bit limbs, as many as its largest value
    needs."""
    if abs_sum < _KERNEL_EXACT:
        return [(1, 0, col)]
    out = []
    for sign, part in ((1, np.maximum(col, 0)), (-1, np.maximum(-col, 0))):
        total = int(part.sum())
        if not total:
            continue
        if total < _KERNEL_EXACT:
            out.append((sign, 0, part))
            continue
        mask = (1 << width) - 1
        for shift in range(0, int(part.max()).bit_length(), width):
            out.append((sign, shift, (part >> shift) & mask))
    return out


def _side_by_side(vals, ids, n_buckets: int, k: int):
    """(K, N) values and the (N,) group ids they share, where ``vals``
    holds K columns of N values one after another, column j's ids being
    column 0's plus j * n_buckets / K; else None."""
    if k == 1 or vals.size % k or n_buckets % k:
        return None
    n, groups = vals.size // k, n_buckets // k
    gids = ids[:n]
    ranks = ids.reshape(k, n) - gids
    if not (ranks == groups * np.arange(k)[:, None]).all():
        return None
    return vals.reshape(k, n), gids


def grouped_reduce(values, bucket_ids, n_buckets: int, *, columns: int = 1,
                   interpret: bool | None = None,
                   stats: dict | None = None):
    """int64 grouped sum for the vectorized SQL engine
    (vector_backend="jax"): per bucket, the sum of the values whose id
    is that bucket. ``columns=K`` says that the buckets are K columns of
    n_buckets / K groups and that ``values`` holds the K columns one
    after another, N values each, column j's ids being column 0's plus
    j * n_buckets / K: the K columns then share the rows of one device
    program. Values laid out otherwise are summed as one column. The
    layout stays flat, (values, ids, n_buckets) as with one column,
    because `bench/control.py`'s faults and the byte count's test wrap
    this function with that signature.
    Integer addition is associative, so an order-free reduction is EXACT
    as long as nothing can overflow:

      * each column is cut into limb columns (``_limbs``) whose sums of
        |v| stay below 2**24: every value and every partial is an exact
        f32 integer, so the bucket_reduce one-hot-matmul kernel
        (full-f32 MXU accumulation) sums all of them at once,
        bit-exactly, and the host adds each column's limbs back, shifted,
        in int64; limbs are as wide as the chunk's rows allow (11 bits
        at 8,192 rows), one when the column's sum of |v| is below 2**24;
      * past 8,192 groups or ``_MAX_LIMBS`` limb columns, an x64 segment
        sum accumulates the columns in int64, with no possible wrap while
        each column's sum(|v|) <= 2**62;
      * otherwise (a column past 2**62) returns None and the caller
        keeps its exact path (the numpy engine falls back to Python
        bigint folds).

    Device errors propagate. ``stats``, when given, counts the program
    run (``kernel_calls`` / ``x64_sums``, or ``device_fallbacks`` per
    column past 2**62), the value columns and limb columns it summed
    (``device_sum_columns`` / ``device_limb_columns``, one limb a column
    in an x64 program), and the rows it summed before and after padding
    (``device_rows`` / ``device_padded_rows``). The call runs under a
    ``flint.grouped_sum`` span (ids ``rows groups cols limbs``), its
    blocking fetch of the sums under ``flint.grouped_sum.fetch``.
    Returns an (n_buckets,) numpy int64 array, or None."""
    vals = np.asarray(values, dtype=np.int64).ravel()
    ids = np.asarray(bucket_ids).ravel()
    aligned = _side_by_side(vals, ids, n_buckets, columns)
    if aligned is None:
        cols, gids, groups = vals[None, :], ids, n_buckets
    else:
        (cols, gids), groups = aligned, n_buckets // columns
    k, n = cols.shape
    with span("flint.grouped_sum", rows=n, groups=groups, cols=k) as sp:
        if n == 0:
            return np.zeros(n_buckets, dtype=np.int64)
        # abs in float64: np.abs of int64 min wraps to a negative value
        abs_sums = np.abs(cols.astype(np.float64)).sum(axis=1)
        over = int(np.count_nonzero(abs_sums > _X64_EXACT))
        if over:
            _count(stats, "device_fallbacks", over)
            return None
        interpret = _interpret() if interpret is None else interpret
        if not interpret:
            use_compile_cache()
        rows = _pow2_at_least(n, _MIN_ROWS)
        pgroups = _pow2_at_least(groups, _MIN_GROUPS)
        # pad rows carry value 0 and id -1: they land in no bucket
        padded_ids = np.full(rows, -1, dtype=np.int32)
        padded_ids[:n] = gids
        limbs = None
        if pgroups <= _MAX_KERNEL_GROUPS:
            width = limb_width(n)
            limbs = [_limbs(c, a, width) for c, a in zip(cols, abs_sums)]
        n_limbs = sum(map(len, limbs)) if limbs is not None else k
        if limbs is not None and n_limbs <= _MAX_LIMBS:
            padded = np.zeros((rows, _pow2_at_least(n_limbs, 1)),
                              dtype=np.float32)
            parts = [limb for col_limbs in limbs for _, _, limb in col_limbs]
            padded[:n, :n_limbs] = np.stack(parts, axis=1)
            out = _kernel_sums(padded, padded_ids, pgroups, interpret)
            _count(stats, "kernel_calls")
        else:
            limbs, n_limbs = None, k
            padded = np.zeros((rows, k), dtype=np.int64)
            padded[:n] = cols.T
            with jax.enable_x64(True):
                out = _x64_sums(padded, padded_ids, pgroups)
            _count(stats, "x64_sums")
        sp.set_metadata(limbs=n_limbs)
        _count(stats, "device_sum_columns", k)
        _count(stats, "device_limb_columns", n_limbs)
        _count(stats, "device_rows", n)
        _count(stats, "device_padded_rows", rows)
        with span("flint.grouped_sum.fetch"):
            out = np.asarray(out).reshape(pgroups, -1)[:groups]
        if limbs is None:
            return out.T.astype(np.int64).reshape(n_buckets)
        sums = np.zeros((k, groups), dtype=np.int64)
        j = 0
        for c, col_limbs in enumerate(limbs):
            for sign, shift, _ in col_limbs:
                part = out[:, j].astype(np.int64) << shift
                sums[c] += part if sign > 0 else -part
                j += 1
        return sums.reshape(n_buckets)


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
