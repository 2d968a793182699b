"""Blocked (flash) attention Pallas kernel for the LM-family architectures.

Streaming-softmax attention tiled for VMEM: q blocks (block_q x head_dim)
stay resident while k/v blocks (block_k x head_dim) stream through the
innermost sequential grid dimension, with running (max, denom, accum)
scratch carried across k blocks.  GQA is handled in the BlockSpec index
maps (query head h reads kv head h // group — no materialized repeat).
Causal q/k block pairs that are entirely masked are skipped with
``pl.when`` (no FLOPs, no DMA use).

MXU alignment: block_q = block_k = 128 by default; head_dim is the matmul
contraction and is 64/128 for every assigned arch.
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

_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, causal: bool, lq: int, lk: int, block_q: int, block_k: int, scale: float
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_rows = iq * block_q + jax.lax.iota(jnp.int32, block_q) + (lk - lq)
    k_cols = ik * block_k + jax.lax.iota(jnp.int32, block_k)
    # skip fully-masked causal blocks: first q row < first k col of block
    run = (not causal) or (iq * block_q + block_q - 1 + (lk - lq)) >= ik * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, dh]
        k = k_ref[0].astype(jnp.float32)            # [bk, dh]
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * scale                       # [bq, bk]
        if causal:
            mask = q_rows[:, None] >= k_cols[None, :]
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)             # finite: m >= _NEG
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        den = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / den).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # [B, Hq, Lq, Dh]
    k: jax.Array,   # [B, Hkv, Lk, Dh]
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    b, hq, lq, dh = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq, bk = min(block_q, lq), min(block_k, lk)
    assert lq % bq == 0 and lk % bk == 0, "pad seq lens to block multiples"
    qf = q.reshape(b * hq, lq, dh)
    kf = k.reshape(b * hkv, lk, dh)
    vf = v.reshape(b * hkv, lk, dh)
    grid = (b * hq, pl.cdiv(lq, bq), pl.cdiv(lk, bk))

    def kv_index(h, iq, ik):
        # query head h -> kv head (h % hq) // group within the same batch
        bi = h // hq
        return (bi * hkv + (h % hq) // group, ik, 0)

    kwargs = {}
    if pltpu is not None and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal, lq=lq, lk=lk,
            block_q=bq, block_k=bk, scale=1.0 / (dh ** 0.5),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, lq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32) if pltpu else None,
            pltpu.VMEM((bq,), jnp.float32) if pltpu else None,
            pltpu.VMEM((bq,), jnp.float32) if pltpu else None,
        ],
        interpret=interpret,
        **kwargs,
    )(qf, kf, vf)
    return out.reshape(b, hq, lq, dh)
