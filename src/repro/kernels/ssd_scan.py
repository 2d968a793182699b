"""Chunked Mamba-2 SSD scan as a Pallas TPU kernel.

State-space duality (arXiv:2405.21060) splits the recurrence into
(a) an intra-chunk quadratic part — dense (Q x Q) and (Q x P) matmuls that
feed the MXU, and (b) an inter-chunk state carry — a [P, N] VMEM scratch
passed along the sequential innermost grid dimension.  Chunk length 128
keeps every matmul MXU-shaped.

    y[i] = sum_{j<=i} (c_i . b_j) exp(cum[i]-cum[j]) dt[j] x[j]   (intra)
         + (c_i . state_prev) exp(cum[i])                         (inter)
    state' = state_prev * exp(cum[Q-1]) + sum_j exp(cum[Q-1]-cum[j]) dt[j] x[j] b_j^T
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *, q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)       # [Q, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # [Q]
    a = a_ref[0].astype(jnp.float32)                # scalar
    b = b_ref[0].astype(jnp.float32)                # [Q, N]
    c = c_ref[0].astype(jnp.float32)                # [Q, N]

    adt = a * dt                                    # [Q] (negative)
    cum = jnp.cumsum(adt)                           # [Q] inclusive
    # intra-chunk: masked decay matrix L[i, j] = exp(cum[i]-cum[j]) dt[j], j <= i
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.iota(jnp.int32, q)
    tri = ii[:, None] >= ii[None, :]
    l_mat = jnp.where(tri, jnp.exp(seg) * dt[None, :], 0.0)
    scores = (c @ b.T) * l_mat                      # [Q, Q]
    y = scores @ x                                  # [Q, P]
    # inter-chunk contribution from carried state
    state = state_ref[...]                          # [P, N]
    y += (c * jnp.exp(cum)[:, None]) @ state.T      # [Q, P]
    # state update
    total = jnp.exp(cum[q - 1])
    w = dt * jnp.exp(cum[q - 1] - cum)              # [Q]
    state_ref[...] = state * total + (x * w[:, None]).T @ b
    o_ref[0, :, 0, :] = y.astype(o_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,      # [B, L, H, P]
    dt: jax.Array,     # [B, L, H]
    a: jax.Array,      # [H]
    b_mat: jax.Array,  # [B, L, N]
    c_mat: jax.Array,  # [B, L, N]
    *,
    chunk: int = 128,
    interpret: bool = True,
) -> jax.Array:
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    q = min(chunk, l)
    assert l % q == 0, "pad seq len to chunk multiple"
    grid = (bsz, h, pl.cdiv(l, q))
    kwargs = {}
    if pltpu is not None and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    return pl.pallas_call(
        functools.partial(_ssd_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, q, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, l, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)] if pltpu else [None],
        interpret=interpret,
        **kwargs,
    )(x, dt, a, b_mat, c_mat)
