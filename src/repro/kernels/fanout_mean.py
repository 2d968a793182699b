"""Pallas TPU kernel for the GCN aggregation hot spot.

``fanout_mean`` — masked mean over the fanout axis of already-gathered
features, x [M, K, D] -> [M, D].  The wrapper views x as [M*K, D] (the
order the GCN's tree levels already have), so one block holds
``block_m`` whole fanout groups; the kernel sums the K strided row
slices, each scaled by its mask column, in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bytes of x one block may hold (double-buffered by the pipeline)
_BLOCK_BYTES = 4 * 1024 * 1024


def _fanout_mean_kernel(x_ref, mask_ref, o_ref, *, k: int):
    m = mask_ref[...]                                  # [bm, K] f32
    num = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(k):                                 # static, K <= fanout
        xj = x_ref[pl.ds(j, o_ref.shape[0], stride=k), :]
        num += xj.astype(jnp.float32) * m[:, j:j + 1]
    den = jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    o_ref[...] = (num / den).astype(o_ref.dtype)


def fanout_mean_pallas(
    x: jax.Array,
    mask: jax.Array,
    *,
    block_m: int = 128,
    block_d: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """Masked mean over the fanout axis: x [M, K, D], mask [M, K] -> [M, D]
    (the oracle is ``ref.fanout_mean_ref``)."""
    m, k, d = x.shape
    # the strided row loads need whole 128-lane tiles on the chip
    bd = block_d if d % block_d == 0 else d
    row_bytes = k * bd * jnp.dtype(x.dtype).itemsize
    bm = max(8, min(block_m, _BLOCK_BYTES // row_bytes) // 8 * 8)
    if bm >= m:
        bm = m
    return pl.pallas_call(
        functools.partial(_fanout_mean_kernel, k=k),
        grid=(pl.cdiv(m, bm), pl.cdiv(d, bd)),
        in_specs=[
            pl.BlockSpec((bm * k, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x.reshape(m * k, d), mask.astype(jnp.float32))
