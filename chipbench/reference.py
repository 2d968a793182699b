"""The plain reference of a training step, shared by every model family:
the padded tree's inputs, the loss and its gradient block by block,
global-norm clipping and Adam, written in ``jax.numpy`` and imported from
nowhere in the program.

A family (``models/<family>.py``) gives the model's equations as
``forward(params, x_seed, x_hops, masks, dtype)`` and the seeds per block
as ``BLOCK``; the loss is the mean negative log-likelihood of the seed
labels.  Adam as configured: global-norm clip, linear warm-up then cosine
decay to a tenth, decoupled weight decay.

Parameters are a flat dict of float32 arrays.  Everything is float32 at
``highest`` matmul precision, unless a lower ``dtype`` is asked for: that
is the control, which computes the forward and backward pass in
``bfloat16``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

def nll_sum(forward, params, x_seed, x_hops, masks, labels,
            dtype=jnp.float32):
    """Summed negative log-likelihood of the seed labels under the
    family's ``forward``."""
    logits = forward(params, x_seed, x_hops, masks, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def _tree(table, labels, seeds, hops, masks):
    """The padded tree's inputs, gathered from the reference's own
    table: padded slots are zero."""
    xs = [table[h] * m[..., None] for h, m in zip(hops, masks)]
    return table[seeds], xs, labels[seeds]


def make_block_grad(forward, dtype=jnp.float32):
    """Jitted ``(params, table, labels, seeds, hops, masks) -> (nll sum,
    grads of it)`` over one block of seeds, at ``highest`` precision."""
    def block(params, table, labels, seeds, hops, masks):
        x_seed, x_hops, y = _tree(table, labels, seeds, hops, masks)
        with jax.default_matmul_precision("highest"):
            s, g = jax.value_and_grad(nll_sum, argnums=1)(
                forward, params, x_seed, x_hops, masks, y, dtype)
        return s.astype(jnp.float32), {k: v.astype(jnp.float32)
                                       for k, v in g.items()}
    return jax.jit(block)


def loss_and_grad(block_fn, params, table, labels, batch, block: int,
                  rows=None):
    """Mean loss and its gradient over a batch of host arrays (``seeds``
    ``[B]``, ``hops``, ``masks``), summed block by block.  ``rows``
    limits the mean to the first ``rows`` seeds (a fault reading)."""
    seeds, hops, masks = batch["seeds"], batch["hops"], batch["masks"]
    n = len(seeds) if rows is None else rows
    total, grads = 0.0, None
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        s, g = block_fn(params, table, labels, jnp.asarray(seeds[lo:hi]),
                        [jnp.asarray(h[lo:hi]) for h in hops],
                        [jnp.asarray(m[lo:hi]) for m in masks])
        total += float(s)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, {k: v / n for k, v in grads.items()}


def clip(grads, max_norm: float):
    """Global-norm clip, as the optimizer gets the gradient."""
    norm = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                         for g in grads.values()))
    scale = min(1.0, max_norm / max(norm, 1e-9))
    return {k: g * scale for k, g in grads.items()}


def adam(train: dict, params, grads, state, step: int):
    """One Adam step (1-based ``step``) on clipped ``grads``; ``state``
    is ``(m, v)`` dicts.  Returns ``(params, state)``."""
    warm = min(step / max(train["warmup_steps"], 1), 1.0)
    span = max(train["total_steps"] - train["warmup_steps"], 1)
    frac = min(max((step - train["warmup_steps"]) / span, 0.0), 1.0)
    lr = train["learning_rate"] * warm * (0.1 + 0.9 * 0.5
                                          * (1.0 + math.cos(math.pi * frac)))
    b1, b2 = train["beta1"], train["beta2"]
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m, v = state
    m = {k: b1 * m[k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(grads[k]) for k in params}
    new = {k: params[k] - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2)
                                                 + train["eps"])
                                + train["weight_decay"] * params[k])
           for k in params}
    return new, (m, v)


def run_steps(train: dict, params0, table, labels, batches, *, forward,
              block: int, dtype=jnp.float32, rows=None):
    """The reference's first ``len(batches)`` training steps from
    ``params0``, with the family's ``forward`` over blocks of ``block``
    seeds.  Returns ``(losses, first clipped gradient, params after the
    last step)``, all on the host."""
    block_fn = make_block_grad(forward, dtype)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
    state = ({k: jnp.zeros_like(v) for k, v in params.items()},
             {k: jnp.zeros_like(v) for k, v in params.items()})
    losses, first = [], None
    for step, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grad(block_fn, params, table, labels, batch,
                                    block, rows)
        grads = clip(grads, train["grad_clip"])
        if first is None:
            first = {k: np.asarray(g) for k, g in grads.items()}
        params, state = adam(train, params, grads, state, step)
        losses.append(loss)
    return losses, first, {k: np.asarray(v) for k, v in params.items()}
