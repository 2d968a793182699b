"""The GCN family: its plain reference equations, its parameters as the
benchmark and the program hold them, and its operation count.

Model (GraphGen+ §3; Kipf & Welling with self and neighbour weights):
tree level ``v`` holds ``x_v``; graph convolution ``i`` updates levels
``0 .. L-i`` as ``relu(h_v W_self + mean_mask(h_{v+1}) W_nbr + b)``, where
the mean runs over the fanout axis and counts only masked-in children
(a parent with none gets 0).  After ``L`` convolutions the seed level goes
through ``W_out, b_out`` to the logits.

Parameters are a flat dict ``{"layers.<i>.w_self": ..., "w_out": ...}``
of float32 arrays.  The configuration's ``model`` gives ``gcn_in_dim``,
``gcn_hidden`` and ``n_classes``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs

#: seeds per block of the reference's forward and backward pass, so
#: that a block's padded tree of 128-wide rows fits beside the table
BLOCK = 512


def init(key, model: dict, depth: int) -> dict:
    """Glorot-uniform weights and zero biases, drawn from ``key``."""
    hidden = model["gcn_hidden"]
    shapes = {}
    din = model["gcn_in_dim"]
    for i in range(depth):
        shapes[f"layers.{i}.w_self"] = (din, hidden)
        shapes[f"layers.{i}.w_nbr"] = (din, hidden)
        shapes[f"layers.{i}.b"] = (hidden,)
        din = hidden
    shapes["w_out"] = (hidden, model["n_classes"])
    shapes["b_out"] = (model["n_classes"],)
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
    """The program's ``GCNParams`` from the benchmark's flat dict."""
    from repro.models.gcn import GCNLayerParams, GCNParams
    layers = tuple(GCNLayerParams(flat[f"layers.{i}.w_self"],
                                  flat[f"layers.{i}.w_nbr"],
                                  flat[f"layers.{i}.b"])
                   for i in range(depth))
    return GCNParams(layers=layers, w_out=flat["w_out"], b_out=flat["b_out"])


def from_program(params) -> dict:
    """The benchmark's flat dict of host arrays from ``GCNParams``."""
    out = {}
    for i, lyr in enumerate(params.layers):
        out[f"layers.{i}.w_self"] = np.asarray(lyr.w_self)
        out[f"layers.{i}.w_nbr"] = np.asarray(lyr.w_nbr)
        out[f"layers.{i}.b"] = np.asarray(lyr.b)
    out["w_out"] = np.asarray(params.w_out)
    out["b_out"] = np.asarray(params.b_out)
    return out


def forward(params, x_seed, x_hops, masks, dtype=jnp.float32):
    """Logits ``[b, n_classes]`` of the model on one padded tree."""
    depth = len(x_hops)
    p = {k: v.astype(dtype) for k, v in params.items()}
    reps = [x_seed.astype(dtype)] + [x.astype(dtype) for x in x_hops]
    for i in range(depth):
        new = []
        for v in range(depth - i):
            m = masks[v].astype(dtype)
            num = jnp.sum(reps[v + 1] * m[..., None], axis=-2)
            den = jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), 1)
            agg = num / den
            new.append(jax.nn.relu(reps[v] @ p[f"layers.{i}.w_self"]
                                   + agg @ p[f"layers.{i}.w_nbr"]
                                   + p[f"layers.{i}.b"]))
        reps = new
    return reps[0] @ p["w_out"] + p["b_out"]


def flops_per_seed(fanouts, model: dict) -> dict:
    """``{"forward": f, "backward": b}`` matrix-product FLOPs per seed.

    Counts the dense transforms of the forward pass and the backward
    operations training needs: the weight gradients of every transform,
    and the input gradients of every transform whose input depends on a
    parameter (not those of the first convolution, whose inputs are
    features).  The masked means are reductions, not matrix products,
    and are not counted."""
    hidden, n_classes = model["gcn_hidden"], model["n_classes"]
    levels = costs.tree_levels(fanouts)
    depth = len(fanouts)
    fwd = bwd = 0
    din = model["gcn_in_dim"]
    for i in range(depth):
        rows = sum(levels[:depth - i])        # levels 0 .. L-i
        f = rows * 2 * (2 * din * hidden)     # w_self and w_nbr
        fwd += f
        bwd += f if i == 0 else 2 * f         # weights; inputs after layer 0
        din = hidden
    f = 2 * hidden * n_classes
    return {"forward": fwd + f, "backward": bwd + 2 * f}
