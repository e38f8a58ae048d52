"""Per-kernel interpret=True sweeps against the pure-jnp oracles, and the
exactness envelopes and bounded shapes of grouped_reduce."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kk,sq,skv,d,causal,window",
    [
        (2, 4, 2, 128, 128, 64, True, 0),     # GQA causal prefill
        (1, 2, 2, 256, 256, 32, True, 64),    # sliding window
        (2, 4, 4, 128, 128, 16, False, 0),    # MHA bidirectional (encoder)
        (1, 8, 2, 128, 384, 64, True, 0),     # decode-style, Sq < Skv
        (1, 2, 1, 64, 64, 128, True, 0),      # MQA
    ])
def test_flash_attention_sweep(b, h, kk, sq, skv, d, causal, window, dtype):
    key = jax.random.PRNGKey(b * 7 + h)
    q = jax.random.normal(key, (b, sq, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, skv, kk, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, skv, kk, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    exp = ref.flash_attention_ref(q.astype(jnp.float32),
                                  k.astype(jnp.float32),
                                  v.astype(jnp.float32),
                                  causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@given(n=st.integers(1, 700), p=st.sampled_from([4, 16, 33]),
       d=st.sampled_from([8, 64]))
@settings(max_examples=10, deadline=None)
def test_bucket_reduce_property(n, p, d):
    """Per-bucket sums == oracle; total mass preserved (nothing lost in
    the 'shuffle')."""
    key = jax.random.PRNGKey(n)
    vals = jax.random.normal(key, (n, d), jnp.float32)
    ids = jax.random.randint(key, (n,), 0, p)
    out = ops.bucket_reduce(vals, ids, p)
    exp = ref.bucket_reduce_ref(vals, ids, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4)
    np.testing.assert_allclose(np.asarray(out.sum(0)),
                               np.asarray(vals.sum(0)), atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,t,d,f", [(4, 64, 32, 48), (2, 128, 128, 128),
                                     (8, 16, 64, 8), (1, 256, 512, 128)])
def test_grouped_matmul_sweep(e, t, d, f, dtype):
    key = jax.random.PRNGKey(e)
    x = jax.random.normal(key, (e, t, d), dtype)
    w = jax.random.normal(jax.random.PRNGKey(9), (e, d, f), dtype)
    out = ops.grouped_matmul(x, w)
    exp = ref.grouped_matmul_ref(x.astype(jnp.float32), w.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=tol * d, rtol=tol)


def test_flash_attention_grad_flows():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 32))
    g = jax.grad(lambda q: ops.flash_attention(q, k, v).sum())(q)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).sum()) > 0


def _bigint_fold(vals, ids, n):
    acc = [0] * n
    for i, v in zip(ids.tolist(), vals.tolist()):
        acc[i] += v
    return acc


def _stacked(cols, ids, groups):
    """K columns as one grouped sum: column j's rows in buckets
    j * groups + id."""
    k = len(cols)
    return (np.concatenate(cols), np.concatenate(
        [np.asarray(ids) + j * groups for j in range(k)]), k * groups)


@pytest.mark.parametrize("case,path", [
    ("small", "kernel_calls"),      # sum(|v|) < 2**24: one limb
    ("near_2_24", "kernel_calls"),  # one partial climbs to 2**24 - 1
    ("limbs", "kernel_calls"),      # up to 2**40: 12-bit limbs
    ("big", "x64_sums"),            # past the kernel's limb columns
    ("wide", "x64_sums"),           # more groups than the kernel takes
    ("over", "device_fallbacks"),   # past 2**62: handed back to the host
    ("int64_min", "device_fallbacks"),
])
def test_grouped_reduce_envelopes_are_exact(case, path):
    """Each envelope of grouped_reduce gives the bigint fold's int64 sums
    (or None past 2**62), and counts the path it took, its columns and
    its limb columns."""
    rng = np.random.default_rng(7)
    n, groups = 3000, 16
    ids = rng.integers(0, groups, size=n)
    cols, limbs = 1, 1
    if case == "small":
        vals = rng.integers(-50, 50, size=n)
    elif case == "near_2_24":
        ids = np.zeros(n, dtype=np.int64)
        vals = rng.integers(1, 2**12, size=n)
        vals[-1] += 2**24 - 1 - int(vals.sum())
    elif case == "limbs":
        vals = rng.integers(-2**40, 2**40, size=n)
        limbs = 8  # 3,000 rows take 12-bit limbs: 4 of each sign
    elif case == "big":
        cols = 5  # 5 x 8 limb columns are more than the kernel takes
        vals, ids, groups = _stacked(
            [rng.integers(-2**40, 2**40, size=n) for _ in range(cols)],
            ids, groups)
        limbs = cols
    elif case == "wide":
        groups = ops._MAX_KERNEL_GROUPS + 1
        ids = np.arange(n) % groups
        vals = rng.integers(-50, 50, size=n)
    elif case == "over":
        vals = np.array([2**62, 2**62] + [1] * (n - 2), dtype=np.int64)
    else:
        vals = np.array([-2**63] + [0] * (n - 1), dtype=np.int64)
    stats = {}
    got = ops.grouped_reduce(vals, ids, groups, columns=cols, stats=stats)
    if path == "device_fallbacks":
        assert stats == {path: 1}
        assert got is None
    else:
        # 3,000 rows pad to the next power of two
        assert stats == {path: 1, "device_rows": n,
                         "device_padded_rows": 4096,
                         "device_sum_columns": cols,
                         "device_limb_columns": limbs}
        assert got.dtype == np.int64
        assert got.tolist() == _bigint_fold(vals, ids, groups)


#: TPC-H Q1's sums at full magnitude (unscaled decimals): quantity at
#: scale 2, extended price, discounted price at scale 4, charge at scale
#: 6 (to 1.1e11), discount
Q1_COLUMNS = ((100, 5000), (90100, 10495000), (810900, 104950000),
              (810900, 113346000000), (0, 10))


@pytest.mark.parametrize("n", [1, 700, 3000, 8192])
@pytest.mark.parametrize("signed", [False, True])
def test_limb_sums_are_exact_at_q1_magnitudes(n, signed):
    """All of a chunk's sums in one kernel program, limb by limb, equal
    numpy's int64 sums: K columns at Q1's magnitudes, signed or not, any
    row count (the padding rows add nothing)."""
    rng = np.random.default_rng(n + signed)
    groups = 4
    ids = rng.integers(0, groups, size=n)
    cols = []
    for lo, hi in Q1_COLUMNS:
        c = rng.integers(lo, hi + 1, size=n)
        if signed:
            c = c * rng.choice([-1, 1], size=n)
        cols.append(c)
    vals, fids, buckets = _stacked(cols, ids, groups)
    stats = {}
    got = ops.grouped_reduce(vals, fids, buckets, columns=len(cols),
                             stats=stats)
    want = np.zeros((len(cols), groups), dtype=np.int64)
    for j, c in enumerate(cols):
        np.add.at(want[j], ids, c)
    assert got.tolist() == want.ravel().tolist()
    assert stats["kernel_calls"] == 1 and "x64_sums" not in stats
    assert stats["device_sum_columns"] == len(cols)
    assert len(cols) <= stats["device_limb_columns"] <= ops._MAX_LIMBS


def test_limb_width_keeps_each_limb_sum_below_2_24():
    for n in (1, 2, 1000, 4096, 8192, 2**24 - 1):
        w = ops.limb_width(n)
        assert n * (2**w - 1) < 2**24 <= n * (2**(w + 1) - 1)
    assert ops.limb_width(8192) == 11
    assert ops.limb_width(2**24) == 0


def test_q1_chunk_is_one_program_of_13_limb_columns():
    """A full 8,192-row chunk of Q1's five distinct sums: quantity in 2
    limbs, price and discounted price in 3, charge in 4, discount whole:
    13 limb columns, one program."""
    rng = np.random.default_rng(3)
    n, groups = 8192, 4
    ids = rng.integers(0, groups, size=n)
    cols = [np.full(n, hi) for _, hi in Q1_COLUMNS]
    vals, fids, buckets = _stacked(cols, ids, groups)
    stats = {}
    got = ops.grouped_reduce(vals, fids, buckets, columns=5, stats=stats)
    assert stats["kernel_calls"] == 1
    assert stats["device_limb_columns"] == 13
    counts = np.bincount(ids, minlength=groups)
    assert got.tolist() == [hi * int(c) for _, hi in Q1_COLUMNS
                            for c in counts]


def test_grouped_reduce_empty_chunk_launches_nothing():
    stats = {}
    got = ops.grouped_reduce(np.array([], dtype=np.int64),
                             np.array([], dtype=np.int64), 4, stats=stats)
    assert got.tolist() == [0, 0, 0, 0] and stats == {}


def test_grouped_reduce_compiles_a_bounded_set_of_shapes():
    """34 chunks of row counts from 1 to 8192 and up to 300 groups compile
    at most 4 row sizes x 3 group sizes of kernel programs."""
    rng = np.random.default_rng(11)
    before = ops.compiled_programs()["kernel"]
    for n in list(rng.integers(1, 8193, size=30)) + [1, 1024, 1025, 8192]:
        groups = int(rng.integers(1, 300))
        ids = rng.integers(0, groups, size=n)
        vals = rng.integers(-100, 100, size=n)
        assert (ops.grouped_reduce(vals, ids, groups).tolist()
                == _bigint_fold(vals, ids, groups))
    assert ops.compiled_programs()["kernel"] - before <= 12


def test_compile_cache_dir_follows_env_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert ops.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = ops.compile_cache_dir()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fixed == os.path.join(root, ".jax_cache")
    assert ops.compile_cache_dir() == fixed
