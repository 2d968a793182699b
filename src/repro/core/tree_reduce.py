"""Tree Reduction (paper §2 step 3).

GraphGen+ organizes workers into a hierarchy so hot-node aggregation is
performed in log(W) partial steps instead of a flat all-to-one.  On a TPU
mesh the natural realization is a **butterfly (recursive-halving) exchange**
built from ``lax.ppermute``: at stage s every worker exchanges its partial
aggregate with the partner ``rank XOR 2^s`` and merges.  After log2(W)
stages every worker holds the full reduction — i.e. tree *allreduce*
semantics, which is what both subgraph aggregation (step 3) and gradient
sync (step 4) need.

The merge operator is a parameter: ``add`` gives a gradient AllReduce;
``merge_topk_samples`` (generation.py) gives distributed reservoir-sample
merging for subgraph candidate sets.  Any associative+commutative op is
valid on a butterfly.

The paper's tree is rack-topology-aware; ICI on a TPU pod is symmetric per
axis, so stage order is the only placement decision (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Callable, TypeVar

import jax
from jax import lax

T = TypeVar("T")


def tree_allreduce(
    x: T,
    merge: Callable[[T, T], T],
    axis_name: str,
) -> T:
    """Butterfly allreduce of pytree ``x`` along ``axis_name`` (size must be
    a power of two — mesh axes here are 2/16) using ``merge`` at each stage."""
    size = lax.axis_size(axis_name)
    if size & (size - 1):
        raise ValueError(f"butterfly needs power-of-two axis, got {size}")
    stage = 1
    while stage < size:
        perm = [(i, i ^ stage) for i in range(size)]
        partner = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), x)
        x = merge(x, partner)
        stage <<= 1
    return x


def tree_psum(x: T, axis_name: str) -> T:
    """Gradient AllReduce via explicit tree reduction (``--grad-sync tree``)."""
    return tree_allreduce(x, lambda a, b: jax.tree.map(lax.add, a, b), axis_name)


def tree_reduce_scatter(
    x: T,
    merge: Callable[[T, T], T],
    axis_name: str,
) -> T:
    """Recursive-halving reduce-scatter along the leading (row) axis.

    Beyond-paper optimization of the subgraph-aggregation tree: the
    butterfly allreduce leaves EVERY worker with the merged result for the
    whole frontier (log2(W) full-width stages), but the balance table
    assigns each worker a contiguous 1/W row segment — only that segment is
    needed.  Recursive halving exchanges the half of the current segment
    the partner's group owns and merges the half it keeps, so per-worker
    traffic drops from log2(W) * F rows to (1 - 1/W) * F rows (~4x at
    W=16), and merge compute shrinks geometrically.

    Every leaf of ``x`` must have the same leading dimension F (divisible
    by the axis size); returns the fully-merged rows ``me*F/W : (me+1)*F/W``
    for each worker (big-endian rank-bit segment ordering).
    """
    size = lax.axis_size(axis_name)
    if size & (size - 1):
        raise ValueError(f"recursive halving needs power-of-two axis, got {size}")
    me = lax.axis_index(axis_name)
    n_stages = size.bit_length() - 1
    seg = x
    for b in reversed(range(n_stages)):
        f = jax.tree.leaves(seg)[0].shape[0]
        half = f // 2
        mybit = (me >> b) & 1
        keep = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, mybit * half, half, 0), seg
        )
        send = jax.tree.map(
            lambda a: lax.dynamic_slice_in_dim(a, (1 - mybit) * half, half, 0), seg
        )
        perm = [(i, i ^ (1 << b)) for i in range(size)]
        recv = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), send)
        seg = merge(keep, recv)
    return seg
