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


@pytest.mark.parametrize("case,path", [
    ("small", "kernel_calls"),      # sum(|v|) < 2**24: the one-hot kernel
    ("near_2_24", "kernel_calls"),  # one partial climbs to 2**24 - 1
    ("big", "x64_sums"),            # up to 2**62: the x64 segment sum
    ("wide", "x64_sums"),           # more groups than the kernel takes
    ("over", "device_fallbacks"),   # past 2**62: handed back to the host
    ("int64_min", "device_fallbacks"),
])
def test_grouped_reduce_envelopes_are_exact(case, path):
    """Each envelope of grouped_reduce gives the bigint fold's int64 sums
    (or None past 2**62), and counts the path it took."""
    rng = np.random.default_rng(7)
    n, groups = 3000, 16
    ids = rng.integers(0, groups, size=n)
    if case == "small":
        vals = rng.integers(-50, 50, size=n)
    elif case == "near_2_24":
        ids = np.zeros(n, dtype=np.int64)
        vals = rng.integers(1, 2**12, size=n)
        vals[-1] += 2**24 - 1 - int(vals.sum())
    elif case == "big":
        vals = rng.integers(-2**40, 2**40, size=n)
    elif case == "wide":
        groups = ops._MAX_KERNEL_GROUPS + 1
        ids = np.arange(n) % groups
        vals = rng.integers(-50, 50, size=n)
    elif case == "over":
        vals = np.array([2**62, 2**62] + [1] * (n - 2), dtype=np.int64)
    else:
        vals = np.array([-2**63] + [0] * (n - 1), dtype=np.int64)
    stats = {}
    got = ops.grouped_reduce(vals, ids, groups, stats=stats)
    if path == "device_fallbacks":
        assert stats == {path: 1}
        assert got is None
    else:
        # 3,000 rows pad to the next power of two
        assert stats == {path: 1, "device_rows": n,
                         "device_padded_rows": 4096}
        assert got.dtype == np.int64
        assert got.tolist() == _bigint_fold(vals, ids, groups)


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
