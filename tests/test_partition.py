"""Graph Partitioning (paper step 1): completeness and balance."""
import numpy as np
import pytest

from repro.core.partition import partition_edges
from repro.graph.csr import CSRGraph
from repro.graph.synthetic import powerlaw_graph


@pytest.mark.parametrize("strategy", ["by_edge_hash", "by_src_block"])
def test_partition_preserves_every_edge(strategy):
    g = powerlaw_graph(500, avg_degree=6, seed=1)
    part = partition_edges(g, 4, strategy=strategy)
    global_edges = sorted(zip(*g.edge_list()))
    local_edges = []
    for w in range(4):
        local = CSRGraph(part.indptr[w], part.indices[w][: part.n_local[w]])
        # indices were padded; rebuild edge list from local indptr
        src = np.repeat(np.arange(g.n_nodes, dtype=np.int32),
                        np.diff(part.indptr[w]))
        dst = part.indices[w][: len(src)]
        local_edges += list(zip(src.tolist(), dst.tolist()))
    assert sorted(local_edges) == global_edges


def test_edge_hash_splits_hot_nodes():
    """Edge-centric partitioning must spread a hot node's edges across
    workers — the property that parallelizes hot-node collection."""
    g = powerlaw_graph(300, avg_degree=4, n_hot=1, hot_degree=120, seed=0)
    part = partition_edges(g, 4, strategy="by_edge_hash")
    hot = int(np.argmax(g.degrees()))
    local_deg = [part.indptr[w][hot + 1] - part.indptr[w][hot] for w in range(4)]
    assert all(d > 0 for d in local_deg)           # every worker holds a share
    assert max(local_deg) < g.degrees()[hot]       # nobody holds it all


@pytest.mark.parametrize("n,avg", [(20_000, 10.0), (200_000, 25.0)])
def test_default_hubs_keep_edge_count_bounded(n, avg):
    """The launchers' hubs (n // 1000 of them, no ``hot_degree``) stay the
    highest-degree nodes without multiplying the edge count at scale."""
    n_hot = n // 1000
    g = powerlaw_graph(n, avg_degree=avg, n_hot=n_hot, seed=0)
    assert len(g.indices) <= 1.5 * n * avg
    deg = np.sort(g.degrees())
    assert deg[-n_hot] > deg[-n_hot - 1]


def test_explicit_hot_degree_graph_unchanged():
    """A caller's own ``hot_degree`` plants hubs on the unclipped base
    degrees, exactly as the configuration model always has."""
    n, avg, n_hot, hot, seed = 3000, 8.0, 3, 500, 4
    rng = np.random.default_rng(seed)
    raw = np.minimum(rng.zipf(2.1, size=n).astype(np.float64), n // 2)
    deg = np.maximum((raw * (avg / raw.mean())).astype(np.int64), 1)
    deg[rng.choice(n, size=n_hot, replace=False)] = hot
    src = np.repeat(np.arange(n, dtype=np.int32), deg)
    dst = rng.integers(0, n, size=len(src), dtype=np.int32)
    want = CSRGraph.from_edges(src, dst, n)
    g = powerlaw_graph(n, avg_degree=avg, n_hot=n_hot, hot_degree=hot,
                       seed=seed)
    np.testing.assert_array_equal(g.indptr, want.indptr)
    np.testing.assert_array_equal(g.indices, want.indices)


def test_edge_hash_balances_better_than_src_block():
    g = powerlaw_graph(2000, avg_degree=8, n_hot=5, hot_degree=400, seed=2)
    ph = partition_edges(g, 8, strategy="by_edge_hash")
    pb = partition_edges(g, 8, strategy="by_src_block")
    assert ph.edge_balance() <= pb.edge_balance()
    assert ph.edge_balance() < 1.05
