"""Synthetic industrial-graph generators.

The paper evaluates on a 530M-node / 5B-edge production graph with a heavy
power-law degree distribution (hot nodes are the motivating problem for the
tree-reduction strategy).  We generate scale-down analogues with the same
statistical shape: a Zipf-distributed out-degree sequence realized with a
configuration model, plus optional planted "hot" nodes.
"""
from __future__ import annotations

import numpy as np

from .csr import CSRGraph

#: default share of the base edge count that the planted hubs hold
#: together, when no ``hot_degree`` is given
HUB_EDGE_SHARE = 0.25


def powerlaw_graph(
    n_nodes: int,
    avg_degree: float = 10.0,
    alpha: float = 2.1,
    n_hot: int = 0,
    hot_degree: int = 0,
    seed: int = 0,
) -> CSRGraph:
    """Directed power-law graph via a configuration model.

    ``n_hot`` nodes are planted with out-degree ``hot_degree`` to stress the
    hot-node aggregation path (paper §2 step 3).  Without a ``hot_degree``
    the hubs share ``HUB_EDGE_SHARE * n_nodes * avg_degree`` edges and the
    natural degrees are clipped just below theirs, so the hubs keep the
    largest degrees and the edge count stays within 1.25x the base graph's
    at any scale.
    """
    rng = np.random.default_rng(seed)
    # Zipf-ish degrees clipped so the expected mean is ~avg_degree.
    raw = rng.zipf(alpha, size=n_nodes).astype(np.float64)
    raw = np.minimum(raw, n_nodes // 2)
    deg = np.maximum((raw * (avg_degree / raw.mean())).astype(np.int64), 1)
    if n_hot > 0:
        hot_ids = rng.choice(n_nodes, size=n_hot, replace=False)
        if not hot_degree:
            hot_degree = max(int(HUB_EDGE_SHARE * n_nodes * avg_degree
                                 / n_hot), 2)
            deg = np.minimum(deg, hot_degree - 1)
        deg[hot_ids] = hot_degree
    src = np.repeat(np.arange(n_nodes, dtype=np.int32), deg)
    dst = rng.integers(0, n_nodes, size=len(src), dtype=np.int32)
    return CSRGraph.from_edges(src, dst, n_nodes)


def node_features(n_nodes: int, dim: int, seed: int = 0, *,
                  features_on_host: bool = False,
                  chunk_rows: int = 1 << 16) -> np.ndarray:
    """Synthetic [n_nodes, dim] float32 feature table.

    With ``features_on_host=True`` the table is built for the L3 host
    store (``core/host_store.py``): generated in ``chunk_rows``-row
    chunks into one preallocated host array, so peak memory is the table
    itself plus ONE chunk — the default path's full-size ``* 0.1``
    temporary would double the footprint, which is exactly what a
    table sized beyond aggregate device memory cannot afford.  Both
    paths are bit-identical: sequential ``standard_normal`` chunk draws
    consume the Generator stream exactly like one full-size draw, and
    the in-place ``*= 0.1`` is the same float32 multiply.
    """
    rng = np.random.default_rng(seed + 1)
    if not features_on_host:
        return rng.standard_normal((n_nodes, dim), dtype=np.float32) * 0.1
    out = np.empty((n_nodes, dim), np.float32)
    for lo in range(0, n_nodes, chunk_rows):
        hi = min(lo + chunk_rows, n_nodes)
        out[lo:hi] = rng.standard_normal((hi - lo, dim), dtype=np.float32)
    out *= np.float32(0.1)
    return out


def node_labels(n_nodes: int, n_classes: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed + 2)
    return rng.integers(0, n_classes, size=n_nodes, dtype=np.int32)
