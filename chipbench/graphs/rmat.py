"""Graph500 R-MAT (Kronecker) graph, stored in both directions.

Each of ``edge_factor * 2**scale`` edges picks one quadrant of the
adjacency matrix per level with probabilities ``a, b, c`` and ``1-a-b-c``
(Graph500 specification, "Kronecker generator").  Vertex ids are then
relabelled by a seeded permutation, so the heavy rows are spread over the
id space, and every edge is stored as ``u -> v`` and ``v -> u``.
Duplicates and self loops are kept, as the specification keeps them.

Returns a CSR whose neighbour lists are sorted.
"""
from __future__ import annotations

import numpy as np

from chipbench.datasets import csr_from_edges


def build(params: dict, seed: int):
    """``(indptr, indices)`` of the R-MAT graph that ``params`` and
    ``seed`` define (``scale``, ``edge_factor``, ``a``, ``b``, ``c``)."""
    scale = int(params["scale"])
    n = 1 << scale
    m = int(params["edge_factor"]) * n
    a, b, c = (np.float32(params[k]) for k in ("a", "b", "c"))
    rng = np.random.default_rng(seed)
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    for level in range(scale):
        r = rng.random(m, dtype=np.float32)
        row_bit = r >= a + b
        col_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= row_bit.astype(np.int32) << level
        dst |= col_bit.astype(np.int32) << level
    perm = rng.permutation(n).astype(np.int32)
    src, dst = perm[src], perm[dst]
    return csr_from_edges(np.concatenate([src, dst]),
                          np.concatenate([dst, src]), n)
