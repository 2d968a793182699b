"""GAT (Veličković et al., ICLR 2018, arXiv:1710.10903, eqs. 1-5) over
fixed-fanout padded subgraph trees — the attention-weighted counterpart
of ``models/gcn.py`` on the same ``SubgraphBatch``.

Layer ``l`` (1-based) updates tree levels ``0 .. L-l`` as ``gcn_forward``
does.  With ``K`` heads of width ``F`` and input width ``d``:

* projection: ``z_u = h_u W`` with ``W: [d, K*F]``, viewed as ``[K, F]``;
* scores: ``e_vu^k = LeakyReLU_0.2(a_dst^k . z_v^k + a_src^k . z_u^k)`` for
  ``u`` in ``{v}`` and the masked-in children of ``v`` (self is included,
  as in GAT's ``N_i``, so a parent with no children attends to itself);
* weights: ``alpha_vu^k`` is the softmax of ``e_vu^k`` over that set;
* output: ``h_v' = concat_k(sum_u alpha_vu^k z_u^k) + b``, then ReLU after
  every layer but the last.

The seed level's last representation goes through ``W_out, b_out`` to
the logits.  Dropout is left out (the step stays deterministic).  Each
layer runs under ``jax.named_scope("gat_project")`` (the ``W`` products
over levels ``0 .. L-l+1``) and ``"gat_attend"`` (scores, softmax and
weighted sum); the head runs under ``"gat_out"``.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..graph.subgraph import SubgraphBatch

#: LeakyReLU slope of the attention scores (GAT's 0.2)
NEGATIVE_SLOPE = 0.2


class GATLayerParams(NamedTuple):
    w: jax.Array        # [d, K*F]
    a_src: jax.Array    # [K, F]
    a_dst: jax.Array    # [K, F]
    b: jax.Array        # [K*F]


class GATParams(NamedTuple):
    layers: Tuple[GATLayerParams, ...]   # one per hop, first applied first
    w_out: jax.Array
    b_out: jax.Array


def init_gat(cfg: ModelConfig, rng: jax.Array) -> GATParams:
    """Glorot-uniform weights (``a_src``, ``a_dst`` over ``[K, F]``) and
    zero biases, for ``cfg.gat_heads`` heads of ``gcn_hidden // gat_heads``."""
    d, h, c = cfg.gcn_in_dim, cfg.gcn_hidden, cfg.n_classes
    heads = cfg.gat_heads
    depth = max(len(cfg.fanouts), 1)
    ks = jax.random.split(rng, 3 * depth + 1)
    gl = jax.nn.initializers.glorot_uniform()
    layers = []
    din = d
    for i in range(depth):
        layers.append(GATLayerParams(
            w=gl(ks[3 * i], (din, h)),
            a_src=gl(ks[3 * i + 1], (heads, h // heads)),
            a_dst=gl(ks[3 * i + 2], (heads, h // heads)),
            b=jnp.zeros((h,)),
        ))
        din = h
    return GATParams(layers=tuple(layers), w_out=gl(ks[-1], (h, c)),
                     b_out=jnp.zeros((c,)))


def _attend(z_v: jax.Array, z_c: jax.Array, mask: jax.Array,
            lyr: GATLayerParams) -> jax.Array:
    """One level's attention: parents ``z_v [..., K*F]`` over themselves
    and their children ``z_c [..., k, K*F]`` (``mask [..., k]``) ->
    ``[..., K*F]`` before the bias."""
    heads, f = lyr.a_src.shape
    zv = z_v.reshape(z_v.shape[:-1] + (heads, f))
    zc = z_c.reshape(z_c.shape[:-1] + (heads, f))
    dst = jnp.sum(zv * lyr.a_dst, axis=-1)                  # [..., K]
    e_self = jax.nn.leaky_relu(dst + jnp.sum(zv * lyr.a_src, axis=-1),
                               NEGATIVE_SLOPE)
    e_child = jax.nn.leaky_relu(dst[..., None, :]
                                + jnp.sum(zc * lyr.a_src, axis=-1),
                                NEGATIVE_SLOPE)             # [..., k, K]
    e_child = jnp.where(mask[..., None], e_child, -jnp.inf)
    # the self term keeps every row's max, and so its softmax, finite;
    # the shift cancels in the softmax, so no gradient flows through it
    top = jax.lax.stop_gradient(jnp.maximum(e_self,
                                            jnp.max(e_child, axis=-2)))
    p_self = jnp.exp(e_self - top)
    p_child = jnp.exp(e_child - top[..., None, :])
    den = p_self + jnp.sum(p_child, axis=-2)
    num = p_self[..., None] * zv + jnp.sum(p_child[..., None] * zc, axis=-3)
    out = num / den[..., None]
    return out.reshape(z_v.shape)


def gat_forward(params: GATParams, batch: SubgraphBatch) -> jax.Array:
    """Bottom-up attention over an L-hop batch: hop L -> ... -> seed;
    returns the logits ``[b, n_classes]``."""
    depth = batch.depth
    assert len(params.layers) == depth, (
        f"params built for {len(params.layers)} hops, batch has {depth}")
    reps = [batch.x_seed] + list(batch.x_hops)
    for i, lyr in enumerate(params.layers):
        with jax.named_scope("gat_project"):
            z = [r @ lyr.w for r in reps]        # levels 0 .. L-i
        with jax.named_scope("gat_attend"):
            reps = [_attend(z[v], z[v + 1], batch.masks[v], lyr) + lyr.b
                    for v in range(depth - i)]
            if i + 1 < depth:
                reps = [jax.nn.relu(r) for r in reps]
    with jax.named_scope("gat_out"):
        return reps[0] @ params.w_out + params.b_out


def gat_loss(params: GATParams, batch: SubgraphBatch) -> jax.Array:
    """Mean negative log-likelihood of the seed labels."""
    logits = gat_forward(params, batch)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch.labels[:, None], axis=1)[:, 0]
    return nll.mean()
