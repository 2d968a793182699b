"""GCN (Kipf & Welling) over fixed-fanout padded subgraph trees — the
paper's training model (§3: mini-batch GCN, benchmarked at 2-hop (40, 20)).

Depth-generic bottom-up aggregation: an L-hop batch is consumed by L graph
convolutions.  Layer ``i`` (1-based) updates every tree level that still
matters (levels ``0 .. L-i``) from its own representation plus the masked
mean of its children — so the seed level gets the SAME self+neighbor
treatment as interior levels at every layer (the seed repo dropped the
neighbor term at the seed's first layer).  After layer L only the seed
level remains.

Aggregation on a padded fanout tree is a masked mean over the fanout axis
followed by a dense transform — the masked mean routes through
``kernels.ops.fanout_mean``, which picks the Pallas kernel
(kernels/fanout_mean.py) or the reference implementation.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..graph.subgraph import SubgraphBatch
from ..kernels import ops as kops


class GCNLayerParams(NamedTuple):
    w_self: jax.Array
    w_nbr: jax.Array
    b: jax.Array


class GCNParams(NamedTuple):
    layers: Tuple[GCNLayerParams, ...]   # one per hop, deepest first applied
    w_out: jax.Array
    b_out: jax.Array


def init_gcn(cfg: ModelConfig, rng: jax.Array) -> GCNParams:
    d, h, c = cfg.gcn_in_dim, cfg.gcn_hidden, cfg.n_classes
    depth = max(len(cfg.fanouts), 1)
    ks = jax.random.split(rng, 2 * depth + 1)
    gl = jax.nn.initializers.glorot_uniform()
    layers = []
    din = d
    for i in range(depth):
        layers.append(GCNLayerParams(
            w_self=gl(ks[2 * i], (din, h)),
            w_nbr=gl(ks[2 * i + 1], (din, h)),
            b=jnp.zeros((h,)),
        ))
        din = h
    return GCNParams(layers=tuple(layers), w_out=gl(ks[-1], (h, c)),
                     b_out=jnp.zeros((c,)))


def _child_mean(child: jax.Array, mask: jax.Array, use_kernel: bool) -> jax.Array:
    """Masked mean over the last fanout axis: [..., k, D] -> [..., D]."""
    k, d = child.shape[-2], child.shape[-1]
    agg = kops.fanout_mean(
        child.reshape(-1, k, d), mask.reshape(-1, k), use_kernel=use_kernel
    )
    return agg.reshape(child.shape[:-2] + (d,))


def gcn_forward(params: GCNParams, batch: SubgraphBatch, use_kernel: bool = False):
    """Bottom-up tree aggregation over an L-hop batch: hop L -> ... -> seed."""
    depth = batch.depth
    assert len(params.layers) == depth, (
        f"params built for {len(params.layers)} hops, batch has {depth}")
    # reps[v] = current representation of tree level v (0 = seeds)
    reps = [batch.x_seed] + list(batch.x_hops)
    for i, lyr in enumerate(params.layers):
        new_reps = []
        for v in range(depth - i):
            agg = _child_mean(reps[v + 1], batch.masks[v], use_kernel)
            new_reps.append(jax.nn.relu(
                reps[v] @ lyr.w_self + agg @ lyr.w_nbr + lyr.b))
        reps = new_reps
    return reps[0] @ params.w_out + params.b_out  # [b, n_classes]


def gcn_loss(params: GCNParams, batch: SubgraphBatch, use_kernel: bool = False):
    logits = gcn_forward(params, batch, use_kernel=use_kernel)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch.labels[:, None], axis=1)[:, 0]
    return nll.mean()
