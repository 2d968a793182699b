"""Edge-centric generation primitives (single-worker units; the multi-worker
integration runs in test_distributed.py subprocesses), and the stage
scopes the generator's compiled program and profiler traces carry."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import _stage_scopes
from repro.core import generation
from repro.core.baselines import (edge_centric_sample, node_centric_sample,
                                  sql_like_sample)
from repro.core.generation import (Candidates, dedup_requests, fetch_rows,
                                   local_candidates, merge_topk)
from repro.graph.synthetic import powerlaw_graph


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(400, avg_degree=6, n_hot=2, hot_degree=80, seed=0)


def test_local_candidates_are_real_neighbors(graph):
    indptr = jnp.asarray(graph.indptr)
    indices = jnp.asarray(graph.indices)
    frontier = jnp.arange(50, dtype=jnp.int32)
    cand = local_candidates(indptr, indices, frontier, 8, jax.random.PRNGKey(0))
    ids, keys = np.asarray(cand.ids), np.asarray(cand.keys)
    for i in range(50):
        nbrs = set(graph.indices[graph.indptr[i]:graph.indptr[i + 1]].tolist())
        deg = len(graph.indices[graph.indptr[i]:graph.indptr[i + 1]])
        for k in range(8):
            if np.isfinite(keys[i, k]):
                assert ids[i, k] in nbrs
        assert np.isfinite(keys[i]).all() == (deg > 0)


def test_merge_topk_keeps_k_smallest():
    a = Candidates(ids=jnp.array([[1, 2, 3]]), keys=jnp.array([[0.5, 2.0, 9.0]]))
    b = Candidates(ids=jnp.array([[4, 5, 6]]), keys=jnp.array([[0.1, 3.0, jnp.inf]]))
    m = merge_topk(a, b)
    np.testing.assert_allclose(
        sorted(np.asarray(m.keys)[0].tolist()), [0.1, 0.5, 2.0], rtol=1e-6
    )
    assert set(np.asarray(m.ids)[0].tolist()) == {4, 1, 2}


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_merge_topk_associative(seed):
    """Associativity is what licenses the butterfly tree reduction."""
    rng = np.random.default_rng(seed)
    k = 4
    def rand_cand():
        return Candidates(
            ids=jnp.asarray(rng.integers(0, 100, (2, k), dtype=np.int32)),
            keys=jnp.asarray(rng.uniform(0, 10, (2, k)).astype(np.float32)),
        )
    a, b, c = rand_cand(), rand_cand(), rand_cand()
    left = merge_topk(merge_topk(a, b), c)
    right = merge_topk(a, merge_topk(b, c))
    np.testing.assert_allclose(
        np.sort(left.keys, axis=-1), np.sort(right.keys, axis=-1), rtol=1e-6
    )


def test_fetch_rows_single_worker_is_gather():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(1, 1)
    table = jnp.arange(40, dtype=jnp.float32).reshape(20, 2)
    ids = jnp.array([3, 19, 0, 7], dtype=jnp.int32)
    out = shard_map(
        lambda t, i: fetch_rows(t, i, "data"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )(table, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(table)[np.asarray(ids)])


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_dedup_requests_invariants(seed):
    """The static-shape unique front end: each distinct id occupies exactly
    one wire slot (this is what bounds all_to_all traffic by n_unique
    instead of b*(1+k1+k1*k2))."""
    rng = np.random.default_rng(seed)
    r = int(rng.integers(1, 200))
    ids = jnp.asarray(rng.integers(0, 40, r, dtype=np.int32))
    uniq, inverse, valid, n_unique = jax.jit(dedup_requests)(ids)
    uniq, inverse, valid = np.asarray(uniq), np.asarray(inverse), np.asarray(valid)
    n_unique = int(n_unique)
    assert n_unique == len(np.unique(np.asarray(ids)))
    assert valid.sum() == n_unique          # wire slots == distinct ids
    np.testing.assert_array_equal(uniq[inverse], np.asarray(ids))
    assert inverse.max() < n_unique


@pytest.mark.parametrize("ids", [
    np.full(64, 7),                      # all-identical ids
    np.array([13]),                      # single-element input
    np.array([13, 13]),                  # smallest duplicated input
    np.arange(50),                       # already sorted, all distinct
    np.arange(50)[::-1].copy(),          # reverse-sorted, all distinct
    np.array([0, 159, 80, 0, 159, 42]),  # ids spanning the full shard range
    np.array([0]),                       # single id zero (sentinel-adjacent)
], ids=["all-identical", "singleton", "duplicated-pair", "sorted",
        "reverse-sorted", "shard-range", "zero"])
def test_dedup_requests_edge_cases(ids):
    """Boundary inputs for the static-shape unique front end."""
    ids_j = jnp.asarray(ids.astype(np.int32))
    uniq, inverse, valid, n_unique = jax.jit(dedup_requests)(ids_j)
    uniq, inverse, valid = np.asarray(uniq), np.asarray(inverse), np.asarray(valid)
    want = np.unique(ids)
    assert int(n_unique) == len(want)
    assert valid.sum() == len(want)
    np.testing.assert_array_equal(np.sort(uniq[: len(want)]), want)
    np.testing.assert_array_equal(uniq[inverse], ids)
    assert inverse.max() < int(n_unique)


def test_dedup_requests_full_shard_range_routing():
    """Full-table-range ids dedup and fetch correctly at W=1 (the local-
    gather path with dedup telemetry; the ROUTED owner-bucketing version of
    this runs on 8 workers in test_distributed.py)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    w, rows = 1, 160
    mesh = make_local_mesh(w, 1)
    table = jnp.arange(160 * 2, dtype=jnp.float32).reshape(160, 2)
    ids = jnp.asarray([0, 159, 80, 0, 159, 42, 21, 21], jnp.int32)
    out, stats = shard_map(
        lambda t, i: fetch_rows(t, i, "data", return_stats=True),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )(table, ids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(ids)])
    assert int(stats.n_unique) == 5       # {0, 21, 42, 80, 159}
    assert int(stats.n_dropped) == 0


def test_fetch_rows_dedup_matches_naive_single_worker():
    """Shuffled duplicate ids must fetch identical rows via the dedup path
    and the naive path."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(1, 1)
    table = jnp.arange(60, dtype=jnp.float32).reshape(20, 3)
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(0, 20, 64, dtype=np.int32))  # duplicated

    def run(dedup):
        return shard_map(
            lambda t, i: fetch_rows(t, i, "data", dedup=dedup),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
        )(table, ids)

    np.testing.assert_array_equal(np.asarray(run(True)), np.asarray(run(False)))
    np.testing.assert_array_equal(
        np.asarray(run(True)), np.asarray(table)[np.asarray(ids)])


def test_two_hop_semantics_match_seed_layout():
    """Regression: the (40, 20) path through the L-hop engine must keep the
    seed repo's SubgraphBatch node/mask semantics — shapes [B,40]/[B,40,20],
    chained masks, features equal to the table rows wherever masked and
    zeroed wherever padded."""
    from jax.sharding import Mesh
    from repro.core.partition import partition_edges
    from repro.core.generation import make_distributed_generator
    from repro.graph.synthetic import node_features, node_labels

    n, dim, classes, b = 600, 8, 5, 16
    g = powerlaw_graph(n, avg_degree=5, n_hot=2, hot_degree=100, seed=2)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    part = partition_edges(g, 1)
    X = node_features(n, dim)
    Y = node_labels(n, classes)
    gen, dev = make_distributed_generator(mesh, part, X, Y, fanouts=(40, 20))
    batch = jax.tree.map(
        np.asarray,
        gen(dev, jnp.arange(b, dtype=jnp.int32).reshape(1, b),
            jax.random.PRNGKey(0)))
    assert batch.depth == 2 and batch.fanouts == (40, 20)
    # 2-hop convenience views alias the per-hop lists
    assert batch.hop1.shape == (b, 40) and batch.hop2.shape == (b, 40, 20)
    assert batch.mask1.shape == (b, 40) and batch.mask2.shape == (b, 40, 20)
    assert batch.x_hop1.shape == (b, 40, dim)
    assert batch.x_hop2.shape == (b, 40, 20, dim)
    assert batch.nodes_per_iteration() == b * (1 + 40 + 40 * 20)
    # padded parents never spawn children (chained masks)
    assert not (batch.mask2 & ~batch.mask1[..., None]).any()
    # masked hop-1 ids are real neighbors of their seeds
    adj = {v: set(g.indices[g.indptr[v]:g.indptr[v + 1]].tolist())
           for v in batch.seeds}
    for i, s in enumerate(batch.seeds):
        for j in range(40):
            if batch.mask1[i, j]:
                assert batch.hop1[i, j] in adj[s]
    # features: table rows where masked, zeros where padded
    np.testing.assert_array_equal(batch.x_seed, X[batch.seeds])
    m1, m2 = batch.mask1, batch.mask2
    if m1.any():
        np.testing.assert_array_equal(batch.x_hop1[m1], X[batch.hop1[m1]])
    if (~m1).any():
        assert np.abs(batch.x_hop1[~m1]).max() == 0
    if m2.any():
        np.testing.assert_array_equal(batch.x_hop2[m2], X[batch.hop2[m2]])
    if (~m2).any():
        assert np.abs(batch.x_hop2[~m2]).max() == 0
    np.testing.assert_array_equal(batch.labels, Y[batch.seeds])
    assert batch.n_dropped.sum() == 0


def test_baselines_agree_on_sampled_set_validity(graph):
    """All three strategies must return genuine neighbors — they differ in
    COST (the 27x), not in correctness."""
    indptr = jnp.asarray(graph.indptr)
    indices = jnp.asarray(graph.indices)
    src, dst = graph.edge_list()
    frontier = jnp.arange(20, dtype=jnp.int32)
    k = 5
    rng = jax.random.PRNGKey(1)
    adj = {v: set(graph.indices[graph.indptr[v]:graph.indptr[v+1]].tolist())
           for v in range(20)}
    for name, (ids, mask) in {
        "sql": sql_like_sample(jnp.asarray(src), jnp.asarray(dst), frontier, k, rng),
        "node": node_centric_sample(indptr, indices, frontier, k, rng,
                                    max_degree=int(graph.degrees().max())),
        "edge": edge_centric_sample(indptr, indices, frontier, k, rng),
    }.items():
        ids, mask = np.asarray(ids), np.asarray(mask)
        for i in range(20):
            got = set(ids[i][mask[i]].tolist())
            assert got.issubset(adj[i]), (name, i, got, adj[i])
            if adj[i]:
                assert mask[i].any(), (name, i)


# --- stage scopes ----------------------------------------------------------
# Each generation stage is a named scope (``generation.STAGES``); these
# tests read the scopes back from the compiled program and from a profiler
# trace, so a dropped or misplaced scope shows without a chip.

_TESTS = Path(__file__).resolve().parent
#: cases compiled on four virtual CPU devices: the cells' tiers, no cache,
#: the host store, and the reduce-scatter merge's frontier all_gathers
W4_CASES = ("uncached", "sharded", "tiered", "host-tiered", "reduce-scatter")


def _check_report(rep, case, workers):
    missing = _stage_scopes.expected_stages(case, workers) - set(rep["stages"])
    assert not missing, f"{case} W={workers}: no op in stages {missing}"
    assert not rep["unstaged"], \
        f"{case} W={workers}: timed ops in no stage: {rep['unstaged']}"
    assert rep["same_unscoped"], \
        f"{case} W={workers}: the scopes changed the compiled instructions"


@pytest.mark.parametrize("case", sorted(_stage_scopes.CASES))
def test_stage_scopes_cover_the_generator(case):
    """Every stage with work in the compiled generator carries its scope,
    every timed instruction under ``jit(gen_fn)`` sits in a stage, and
    the scopes are metadata only."""
    _check_report(_stage_scopes.report(case), case, 1)


@pytest.fixture(scope="module")
def four_worker_reports():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(_TESTS.parent / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, str(_TESTS / "_stage_scopes.py"), *W4_CASES],
        capture_output=True, text=True, timeout=600, env=env, cwd=_TESTS)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", W4_CASES)
def test_stage_scopes_cover_the_generator_on_four_workers(
        four_worker_reports, case):
    """The same on four workers, where the frontier all_gather, the
    butterfly's collective-permutes and the all_to_all rounds run."""
    _check_report(four_worker_reports[case], case, 4)


def test_stage_of_takes_the_outermost_stage():
    f = _stage_scopes.stage_of
    assert f("jit(step)/jit(gen_fn)/labels/owner_fetch/gather") == "labels"
    assert f("jit(gen_fn)/cache_insert/jit(searchsorted)/vmap()/while") \
        == "cache_insert"
    assert f("jit(gen_fn)/vmap(dedup)/sort") == "dedup"
    assert f("jit(step)/jit(gen_fn)/concatenate") is None
    with pytest.raises(ValueError, match="unknown generation stage"):
        generation._stage("no_such_stage")


def test_stage_times_partition_the_generator_trace(graph, tmp_path):
    """In a profiler trace of the cached generator, the device time of the
    ops under ``jit(gen_fn)`` grouped by outermost stage sums to the
    generator's time: the stages partition it."""
    from repro.core.feature_cache import CacheConfig
    from repro.core.partition import partition_edges
    from repro.graph.synthetic import node_features, node_labels
    from repro.launch.mesh import make_mesh
    sys.path.insert(0, str(_TESTS.parent))
    from chipbench import trace

    mesh = make_mesh((1,), ("data",))
    n = graph.n_nodes
    cfg = CacheConfig(n_rows=64, admit=1, assoc=4, mode="sharded")
    gen, dev, cache = generation.make_distributed_generator(
        mesh, partition_edges(graph, 1), node_features(n, 16),
        node_labels(n, 8), fanouts=(8, 4), cache_cfg=cfg)
    seeds = jnp.arange(64, dtype=jnp.int32)[None]
    names = trace.hlo_op_names(
        gen.lower(dev, seeds, jax.random.PRNGKey(0), cache)
        .compile().as_text())
    for t in range(2):
        cache = gen(dev, seeds, jax.random.PRNGKey(t), cache)[1]
    jax.block_until_ready(cache)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for t in range(3):
                cache = gen(dev, seeds + t, jax.random.PRNGKey(t), cache)[1]
            jax.block_until_ready(cache)
    finally:
        jax.profiler.stop_trace()
    ops, spans = trace.load(trace.find_xplane(str(tmp_path)),
                            {"jit_gen_fn": names})

    def stage(op):
        return _stage_scopes.stage_of(op.scope) \
            if "jit(gen_fn)" in op.scope else "none"
    measured = ("edge_scan", "dedup", "cache_probe", "owner_fetch",
                "cache_insert", "slot_scatter")
    groups = {s: (lambda op, s=s: stage(op) == s) for s in measured}
    groups["gen"] = lambda op: "jit(gen_fn)" in op.scope
    groups["other"] = lambda op: stage(op) not in measured + ("none",)
    g = trace.summarize(ops, spans, "window", groups).groups_s
    assert g["gen"] > 0
    assert g["cache_insert"] > 0 and g["dedup"] > 0 and g["owner_fetch"] > 0
    parts = sum(g[s] for s in measured) + g["other"]
    assert abs(parts - g["gen"]) <= 0.05 * g["gen"], (parts, g)
