"""The datasets: deterministic from the dataset seed, R-MAT's skew at a
small scale, and the built-graph cache."""
import json

import numpy as np
import pytest

from conftest import BENCH_DIR

from chipbench import checks, datasets

RMAT = {"scale": 12, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}


@pytest.mark.parametrize("name,params", [
    ("rmat", RMAT), ("rmat", dict(RMAT, scale=10, edge_factor=8))])
def test_graph_is_a_function_of_its_seed(name, params):
    build = datasets.graph_builder(name)
    a, b, c = build(params, 0), build(params, 0), build(params, 1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    indptr, indices = a
    assert indptr[0] == 0 and indptr[-1] == len(indices)
    # neighbour lists are sorted (the checks search them)
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    key = rows.astype(np.int64) * (len(indptr) - 1) + indices
    assert np.all(np.diff(key) >= 0)


def test_rmat_is_skewed_and_symmetric():
    indptr, indices = datasets.graph_builder("rmat")(RMAT, 0)
    n = 1 << RMAT["scale"]
    assert len(indptr) == n + 1
    assert len(indices) == 2 * RMAT["edge_factor"] * n
    deg = np.diff(indptr)
    top = np.sort(deg)[::-1]
    # a heavy head: the hottest 1% of vertices hold a fifth of the
    # endpoints, and many vertices have no edge at all
    assert top[: n // 100].sum() > 0.2 * deg.sum()
    assert np.mean(deg == 0) > 0.1
    # every edge is stored in both directions
    src = np.repeat(np.arange(n), deg)
    fwd = np.sort(src.astype(np.int64) * n + indices)
    bwd = np.sort(indices.astype(np.int64) * n + src)
    assert np.array_equal(fwd, bwd)


def test_has_edges_binary_search():
    indptr, indices = datasets.csr_from_edges(
        np.array([0, 0, 2, 2, 2]), np.array([3, 1, 0, 4, 1]), 5)
    p = np.array([0, 0, 0, 2, 2, 1, 2])
    c = np.array([1, 3, 2, 4, 0, 0, 3])
    assert checks.has_edges(indptr, indices, p, c).tolist() == [
        True, True, False, True, True, False, False]


def test_check_sample_counts_bad_ids_and_masks():
    indptr, indices = datasets.csr_from_edges(
        np.array([0, 0, 1, 2]), np.array([1, 2, 2, 0]), 4)
    seeds = np.array([0, 3])                  # node 3 has no out-edge
    hops = [np.array([[1, 2], [0, 0]])]
    masks = [np.array([[True, True], [False, False]])]
    assert checks.check_sample(indptr, indices, seeds, hops, masks) == {
        "bad_ids": 0, "bad_masks": 0}
    hops[0][0, 1] = 3                          # not a neighbour of 0
    masks[0][1, 0] = True                      # node 3 cannot have children
    assert checks.check_sample(indptr, indices, seeds, hops, masks) == {
        "bad_ids": 1, "bad_masks": 1}


def test_built_graph_is_kept_and_checked(tmp_path):
    spec = {"graph": "rmat", "seed": 0, "params": dict(RMAT, scale=8),
            "n_edges": 2 * 16 * 256, "feat_dim": 4, "n_classes": 3}
    a = datasets.load_graph(spec, data_dir=tmp_path)
    where = tmp_path / datasets.graph_key(spec)
    assert (where / "indices.npy").is_file()
    b = datasets.load_graph(spec, data_dir=tmp_path)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    with pytest.raises(ValueError, match="edges"):
        datasets.load_graph(dict(spec, n_edges=5), data_dir=tmp_path)
    ds = datasets.load(spec, data_dir=tmp_path)
    assert ds.features.shape == (256, 4) and ds.labels.max() < 3
    again = datasets.load(spec, data_dir=tmp_path)
    assert np.array_equal(ds.features, again.features)
    # the checks draw the same tables again on the device
    x, y = datasets.device_tables(spec, ds.n_nodes)
    assert np.array_equal(np.asarray(x), ds.features)
    assert np.array_equal(np.asarray(y), ds.labels)


def test_configurations_on_one_graph_share_its_build():
    """The graph is kept under a name made from its generator, parameters
    and seed, so every configuration on it reads one build."""
    specs = [json.loads(p.read_text())["dataset"]
             for p in sorted((BENCH_DIR / "configs").glob("*.json"))]
    rmat = [s for s in specs if s["graph"] == "rmat"]
    assert len({datasets.graph_key(s) for s in rmat}) == 1
    one = rmat[0]
    assert datasets.graph_key(dict(one, seed=1)) != datasets.graph_key(one)
    assert datasets.graph_key(dict(one, params=dict(
        one["params"], scale=22))) != datasets.graph_key(one)
