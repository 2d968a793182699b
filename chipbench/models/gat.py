"""The GAT family: its plain reference equations, its parameters as the
benchmark and the program hold them, and its operation count.

Model (Veličković et al., "Graph Attention Networks", ICLR 2018,
arXiv:1710.10903, eqs. 1-5), on the padded tree: level ``v`` holds
``h_v``; layer ``i`` (0-based) has ``K`` heads of width ``F`` and
updates levels ``0 .. L-1-i`` from the levels ``0 .. L-i``:

* ``z_u = h_u W``, ``W: [d, K*F]``, viewed as ``[K, F]``;
* ``e_vu^k = LeakyReLU_0.2(a_dst^k . z_v^k + a_src^k . z_u^k)`` for ``u``
  in ``{v}`` and the masked-in children of ``v``;
* ``alpha_vu^k = softmax_u(e_vu^k)`` over that set (the masked-out
  children at minus infinity);
* ``h_v' = concat_k(sum_u alpha_vu^k z_u^k) + b``, ReLU after every
  layer but the last.

The seed level then goes through ``W_out, b_out`` to the logits.  No
dropout.  Parameters are a flat dict ``{"layers.<i>.w": ...,
"layers.<i>.a_src": ..., "w_out": ...}`` of float32 arrays; the
configuration's ``model`` gives ``gcn_in_dim``, ``gcn_hidden``,
``gat_heads`` and ``n_classes``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs

#: seeds per block of the reference's forward and backward pass: a
#: 64-seed block of the (15, 10, 5) tree is 58,624 rows, 240 MB at 1024
#: wide, which with its projections and gradients fits beside the 4 GiB
#: table
BLOCK = 64


def _shapes(model: dict, depth: int) -> dict:
    hidden, heads = model["gcn_hidden"], model["gat_heads"]
    shapes = {}
    din = model["gcn_in_dim"]
    for i in range(depth):
        shapes[f"layers.{i}.w"] = (din, hidden)
        shapes[f"layers.{i}.a_src"] = (heads, hidden // heads)
        shapes[f"layers.{i}.a_dst"] = (heads, hidden // heads)
        shapes[f"layers.{i}.b"] = (hidden,)
        din = hidden
    shapes["w_out"] = (hidden, model["n_classes"])
    shapes["b_out"] = (model["n_classes"],)
    return shapes


def init(key, model: dict, depth: int) -> dict:
    """Glorot-uniform weights (``a_src``, ``a_dst`` over ``[K, F]``) and
    zero biases, drawn from ``key``."""
    shapes = _shapes(model, depth)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            lim = math.sqrt(6.0 / (shape[0] + shape[1]))
            out[name] = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
    return out


def to_program(flat: dict, model: dict, depth: int):
    """The program's ``GATParams`` from the benchmark's flat dict."""
    from repro.models.gat import GATLayerParams, GATParams
    layers = tuple(GATLayerParams(flat[f"layers.{i}.w"],
                                  flat[f"layers.{i}.a_src"],
                                  flat[f"layers.{i}.a_dst"],
                                  flat[f"layers.{i}.b"])
                   for i in range(depth))
    return GATParams(layers=layers, w_out=flat["w_out"], b_out=flat["b_out"])


def from_program(params) -> dict:
    """The benchmark's flat dict of host arrays from ``GATParams``."""
    out = {}
    for i, lyr in enumerate(params.layers):
        for name in ("w", "a_src", "a_dst", "b"):
            out[f"layers.{i}.{name}"] = np.asarray(getattr(lyr, name))
    out["w_out"] = np.asarray(params.w_out)
    out["b_out"] = np.asarray(params.b_out)
    return out


def forward(params, x_seed, x_hops, masks, dtype=jnp.float32):
    """Logits ``[b, n_classes]`` of the model on one padded tree."""
    depth = len(x_hops)
    p = {k: v.astype(dtype) for k, v in params.items()}
    reps = [x_seed.astype(dtype)] + [x.astype(dtype) for x in x_hops]
    for i in range(depth):
        heads, f = p[f"layers.{i}.a_src"].shape
        z = [(r @ p[f"layers.{i}.w"]).reshape(r.shape[:-1] + (heads, f))
             for r in reps]
        new = []
        for v in range(depth - i):
            # the attention set: the parent itself first, then its children
            zs = jnp.concatenate([z[v][..., None, :, :], z[v + 1]], axis=-3)
            keep = jnp.concatenate(
                [jnp.ones(masks[v].shape[:-1] + (1,), bool), masks[v]],
                axis=-1)
            e = jax.nn.leaky_relu(
                jnp.sum(z[v] * p[f"layers.{i}.a_dst"], axis=-1)[..., None, :]
                + jnp.sum(zs * p[f"layers.{i}.a_src"], axis=-1), 0.2)
            e = jnp.where(keep[..., None], e, -jnp.inf)
            alpha = jax.nn.softmax(e, axis=-2)               # [..., 1+k, K]
            h = jnp.sum(alpha[..., None] * zs, axis=-3)      # [..., K, F]
            h = h.reshape(h.shape[:-2] + (heads * f,)) + p[f"layers.{i}.b"]
            new.append(jax.nn.relu(h) if i + 1 < depth else h)
        reps = new
    return reps[0] @ p["w_out"] + p["b_out"]


def flops_per_seed(fanouts, model: dict) -> dict:
    """``{"forward": f, "backward": b}`` matrix-product FLOPs per seed.

    Counts the projections ``h W`` over the levels each layer reads
    (levels ``0 .. L-i`` for layer ``i``) and the head, and the backward
    operations training needs: the weight gradients of every product, and
    the input gradients of every product whose input depends on a
    parameter (all but the first layer's, whose inputs are features).
    The scores, softmax and weighted sums are element work on ``K*F``-wide
    rows, not matrix products, and are not counted."""
    hidden, n_classes = model["gcn_hidden"], model["n_classes"]
    levels = costs.tree_levels(fanouts)
    depth = len(fanouts)
    fwd = bwd = 0
    din = model["gcn_in_dim"]
    for i in range(depth):
        rows = sum(levels[:depth - i + 1])    # levels 0 .. L-i
        f = rows * 2 * din * hidden
        fwd += f
        bwd += f if i == 0 else 2 * f         # weights; inputs after layer 0
        din = hidden
    f = 2 * hidden * n_classes
    return {"forward": fwd + f, "backward": bwd + 2 * f}
