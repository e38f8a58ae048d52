"""bucket_reduce — Flint's queue shuffle as a TPU kernel.

The paper's C2 pipeline is: hash each record to a partition queue, then
aggregate per partition. On a systolic array that whole pattern collapses
into a one-hot matmul: build the (block, P) dispatch one-hot in VREGs from
an iota==ids compare, and let the MXU do `onehot.T @ values` — "the
shuffle is a matmul" (DESIGN.md §2). This is also exactly the GShard MoE
dispatch primitive, which is why the same kernel services reduceByKey-style
aggregation and expert dispatch.

Grid (N/bn,): the (D, P) accumulator persists in VMEM scratch across the
sequential grid and is written out once, transposed to (P, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, vals_ref, o_ref, acc_ref, *, n_buckets: int, bn: int,
            nblocks: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]  # (bn,) int32; -1 = padding
    vals = vals_ref[...].astype(jnp.float32)  # (bn, d)
    buckets = jax.lax.broadcasted_iota(jnp.int32, (bn, n_buckets), 1)
    onehot = (ids[:, None] == buckets).astype(jnp.float32)  # (bn, P)
    # MXU: (d, bn) @ (bn, P) accumulated in f32 VMEM scratch, buckets on
    # the lanes (a (P, d) accumulator pads d=1 to 128 lanes and runs out of
    # VMEM at 8192 buckets). HIGHEST keeps full-f32 passes: the chip's
    # default precision rounds f32 operands to bf16, which would break the
    # exact integer sums grouped_reduce needs
    acc_ref[...] += jax.lax.dot_general(
        vals, onehot, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(step == nblocks - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bucket_reduce(values, bucket_ids, n_buckets: int, *, block: int = 1024,
                  interpret: bool = False):
    """values: (N, D); bucket_ids: (N,) int32 in [0, n_buckets), -1 for
    padding. Returns per-bucket sums (n_buckets, D).

    ``block`` is 1024 because XLA tiles a 1-D int32 array in HBM by 1024:
    a smaller ``ids`` block does not match that layout and Mosaic refuses
    the kernel on the TPU."""
    n, d = values.shape
    bn = min(block, n)
    pad = (-n) % bn
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        bucket_ids = jnp.pad(bucket_ids, (0, pad), constant_values=-1)
    nblocks = (n + pad) // bn
    return pl.pallas_call(
        functools.partial(_kernel, n_buckets=n_buckets, bn=bn,
                          nblocks=nblocks),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((d, n_buckets), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, n_buckets), values.dtype),
        scratch_shapes=[pltpu.VMEM((d, n_buckets), jnp.float32)],
        interpret=interpret,
    )(bucket_ids, values).T
