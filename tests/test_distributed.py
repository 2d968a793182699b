"""Multi-worker integration tests.

These run in SUBPROCESSES with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps its single real device (the dry-run-only
rule for forced device counts).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forced(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    prologue = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", prologue + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_distributed_generation_validity():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh

        W = 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(2000, avg_degree=8, n_hot=3, hot_degree=500, seed=0)
        part = partition_edges(g, W)
        X = node_features(2000, 32); Y = node_labels(2000, 7)
        table = balance_table(np.arange(2000), W, seed=0)
        seeds = table.per_worker[:, :16]
        gen, dev = make_distributed_generator(mesh, part, X, Y, fanouts=(8, 4))
        b = jax.tree.map(np.asarray, gen(dev, jnp.asarray(seeds), jax.random.PRNGKey(0)))
        adj = {v: set(g.indices[g.indptr[v]:g.indptr[v+1]]) for v in b.seeds}
        for i, s in enumerate(b.seeds):
            for j in range(8):
                if b.mask1[i, j]:
                    assert b.hop1[i, j] in adj[s], (i, j)
        assert np.abs(b.x_hop1[b.mask1] - X[b.hop1[b.mask1]]).max() == 0
        assert np.abs(b.x_seed - X[b.seeds]).max() == 0
        assert (b.labels == Y[b.seeds]).all()
        assert b.mask1.mean() == 1.0
        print("VALID")
    """)
    assert "VALID" in out


def test_hot_node_sampling_is_unbiased_across_partitions():
    """A hot node's edges live on all 8 workers; the tree-merged sample must
    draw from across the whole partition set, not just one worker."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.csr import CSRGraph
        from repro.core.partition import partition_edges
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh

        W = 8
        # star graph: node 0 -> 1..800 (hot), everyone else isolated
        src = np.zeros(800, dtype=np.int32)
        dst = np.arange(1, 801, dtype=np.int32)
        g = CSRGraph.from_edges(src, dst, 801)
        part = partition_edges(g, W)   # edge-hash splits the hot edge list
        X = np.zeros((801, 4), np.float32); Y = np.zeros(801, np.int32)
        mesh = make_mesh((W,), ("data",))
        gen, dev = make_distributed_generator(mesh, part, X, Y, fanouts=(16, 2))
        seeds = np.zeros((W, 4), np.int32)   # every worker asks about node 0
        seen = set()
        for t in range(16):
            b = gen(dev, jnp.asarray(seeds), jax.random.PRNGKey(t))
            ids = np.asarray(b.hop1)[np.asarray(b.mask1)]
            # which worker-partition did each sampled edge come from?
            seen.update((int(i) % W) for i in ids)
        assert len(seen) == W, f"samples only from partitions {sorted(seen)}"
        print("UNBIASED", sorted(seen))
    """)
    assert "UNBIASED" in out


def test_tree_allreduce_matches_psum():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.tree_reduce import tree_psum
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        x = jnp.arange(8 * 5, dtype=jnp.float32).reshape(8, 5)
        tree = shard_map(lambda v: tree_psum(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"),
                         check_vma=False)(x)
        flat = shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"),
                         check_vma=False)(x)
        np.testing.assert_allclose(np.asarray(tree), np.asarray(flat))
        print("TREE_OK")
    """)
    assert "TREE_OK" in out


def test_fetch_rows_multiworker_routes_correctly():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, rows, d = 8, 16, 3
        mesh = make_mesh((W,), ("data",))
        table = np.arange(W * rows * d, dtype=np.float32).reshape(W * rows, d)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, W * rows, size=64).astype(np.int32)
        out = shard_map(lambda t, i: fetch_rows(t, i, "data"),
                        mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
                        check_vma=False)(jnp.asarray(table), jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(out), table[ids])
        print("FETCH_OK")
    """)
    assert "FETCH_OK" in out


def test_fetch_rows_skew_reports_drops_and_dedup_avoids_them():
    """Capacity overflow: a fully-skewed request pattern (every id owned by
    worker 0, heavily duplicated) must REPORT drops through FetchStats, not
    silently zero-fill; the dedup front end collapses the duplicates so at
    most n_unique ids cross the all_to_all and nothing drops at
    capacity == n_unique."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, rows, d = 8, 16, 3
        mesh = make_mesh((W,), ("data",))
        table = np.arange(W * rows * d, dtype=np.float32).reshape(W * rows, d)
        rng = np.random.default_rng(0)
        # 256 requests over the 16 rows of worker 0 -> n_unique == 16
        ids = rng.integers(0, rows, size=256).astype(np.int32)

        def run(dedup, capacity):
            return shard_map(
                lambda t, i: fetch_rows(t, i, "data", dedup=dedup,
                                        capacity=capacity, return_stats=True),
                mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
                check_vma=False)(jnp.asarray(table), jnp.asarray(ids))

        n_unique = len(np.unique(ids))
        assert n_unique == 16
        # naive path at the dedup-sized capacity: massive drops, all counted
        out_n, st_n = run(False, n_unique)
        assert int(st_n.n_dropped) == 256 - n_unique, st_n
        # dedup path: every distinct id crosses once -> zero drops, and the
        # zero-filled naive result differs from the correct dedup result
        out_d, st_d = run(True, n_unique)
        assert int(st_d.n_unique) == n_unique
        assert int(st_d.n_dropped) == 0
        np.testing.assert_array_equal(np.asarray(out_d), table[ids])
        # naive path with the same wire budget lost rows
        assert np.abs(np.asarray(out_n) - table[ids]).max() > 0
        # under-capacity dedup: n_dropped counts zero-filled request SLOTS
        # (every duplicate of a dropped unique id), not wire slots
        out_p, st_p = run(True, 8)
        zero_filled = (np.asarray(out_p) != table[ids]).any(axis=1).sum()
        assert int(st_p.n_dropped) == zero_filled > 0, (st_p, zero_filled)
        print("DEDUP_OK")
    """)
    assert "DEDUP_OK" in out


def test_fetch_rows_shard_boundary_ids_route_correctly():
    """Ids sitting exactly on shard boundaries (first/last row of every
    worker's block), heavily duplicated, must route to the right owner and
    dedup to one wire slot each — the `owner = id // rows` bucketing at the
    edges is exactly where an off-by-one would hide."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, rows, d = 8, 16, 3
        mesh = make_mesh((W,), ("data",))
        table = np.arange(W * rows * d, dtype=np.float32).reshape(W * rows, d)
        # first and last row of every shard, plus global extremes, duplicated
        edges = [k * rows for k in range(W)] + [k * rows + rows - 1 for k in range(W)]
        ids = np.asarray(edges * 3 + [0, W * rows - 1], dtype=np.int32)
        out, stats = shard_map(
            lambda t, i: fetch_rows(t, i, "data", return_stats=True),
            mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
            check_vma=False)(jnp.asarray(table), jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(out), table[ids])
        assert int(stats.n_unique) == len(set(edges))
        assert int(stats.n_dropped) == 0
        print("BOUNDARY_OK")
    """)
    assert "BOUNDARY_OK" in out


#: the cross-mode differential matrix: every cache placement x every
#: associativity x every worker count x every probe wire format, each
#: cell checked bit-for-bit against the uncached oracle (the raw host
#: feature table) AND for training-loss equality — the single harness
#: that replaces the old scattered per-mode bit-identity tests
CACHE_MODES = ("none", "replicated", "sharded", "tiered")
CACHE_WIRES = ("dense", "compact")


@pytest.mark.parametrize("w", [1, 2, 4])
@pytest.mark.parametrize("assoc", [1, 2, 4])
@pytest.mark.parametrize("wire", CACHE_WIRES)
@pytest.mark.parametrize("mode", CACHE_MODES)
def test_cross_mode_differential_matrix(mode, wire, assoc, w):
    """THE cache contract, swept as one property over the whole design
    space: for every mode x assoc x W x wire cell, the generation
    engine's fetched feature rows are bit-identical to the uncached
    oracle (features gathered straight from the host table), padded
    slots are exactly zero, labels match, nothing drops, and the
    training loss computed from the generated batch equals the loss
    computed from the oracle batch bit-for-bit.  Recurring rngs warm the
    cache so every cached cell also proves hits appear without
    perturbing the rows; the compact cells run with a DELIBERATELY tiny
    hit_cap so demotion itself is inside the bit-identity sweep."""
    if wire == "compact" and (mode in ("none", "replicated") or w == 1):
        pytest.skip("no shard-probe round to compact in this cell")
    out = run_forced(f"""
        MODE, ASSOC, W, WIRE = {mode!r}, {assoc}, {w}, {wire!r}
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh
        from repro.models import gcn as gcn_mod

        N, D, C = 600, 8, 7
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(N, avg_degree=8, n_hot=3, hot_degree=200, seed=0)
        part = partition_edges(g, W)
        X = node_features(N, D); Y = node_labels(N, C)
        table = balance_table(np.arange(N), W, seed=0)
        seeds = jnp.asarray(table.per_worker[:, :6])
        # compact cells pin hit_cap=4 — far below the warm hit count, so
        # holder-side demotion provably fires inside the identity sweep
        cc = None if MODE == "none" else CacheConfig(
            128, admit=1, assoc=ASSOC, mode=MODE,
            l1_rows=32 if MODE == "tiered" else 0, l1_promote=2,
            wire=WIRE, hit_cap=4 if WIRE == "compact" else 0)
        out = make_distributed_generator(mesh, part, X, Y, fanouts=(5, 3),
                                         cache_cfg=cc)
        gen, dev = out[0], out[1]
        cache = out[2] if cc is not None else None
        mcfg = dataclasses.replace(get_config("graphgen-gcn"), gcn_in_dim=D,
                                   gcn_hidden=16, n_classes=C, fanouts=(5, 3))
        params = gcn_mod.init_gcn(mcfg, jax.random.PRNGKey(1))
        loss_fn = jax.jit(gcn_mod.gcn_loss)
        hits = 0
        for t in range(3):
            rng = jax.random.PRNGKey(t % 2)   # recurring ids warm the cache
            if cache is None:
                b = gen(dev, seeds, rng)
            else:
                b, cache = gen(dev, seeds, rng, cache)
            b = jax.tree.map(np.asarray, b)
            assert b.n_dropped.sum() == 0, b.n_dropped
            # --- bit-identical rows vs the uncached oracle (the table) ---
            np.testing.assert_array_equal(b.x_seed, X[b.seeds])
            oracle_hops = []
            for h, m, x in zip(b.hops, b.masks, b.x_hops):
                want = X[h] * m[..., None]          # padded slots exactly 0
                np.testing.assert_array_equal(x, want)
                oracle_hops.append(want)
            assert (b.labels == Y[b.seeds]).all()
            # --- bit-identical training loss vs the oracle batch ---------
            oracle = b._replace(x_seed=X[b.seeds],
                                x_hops=tuple(oracle_hops))
            l_got = np.asarray(loss_fn(params, jax.tree.map(jnp.asarray, b)))
            l_want = np.asarray(loss_fn(params,
                                        jax.tree.map(jnp.asarray, oracle)))
            assert l_got.tobytes() == l_want.tobytes(), (l_got, l_want)
            assert np.isfinite(l_got)
            hits += int(b.n_cache_hits.sum())
        if cc is not None:
            assert hits > 0, "cache never warmed on recurring ids"
        else:
            assert hits == 0
        print("MATRIX_OK", MODE, ASSOC, W, WIRE, hits)
    """, devices=w)
    assert "MATRIX_OK" in out


#: the host-store (L3) extension of the matrix: the same bit-identity
#: and loss-equality contract, but with the feature table in host RAM —
#: the generation step emits staged misses, the HostFeatureStore gathers
#: them, and patch_batch must reconstruct the exact device-resident rows
HOST_MODES = ("none", "replicated", "sharded", "tiered")


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("mode", HOST_MODES)
def test_host_store_differential_cells(mode, w):
    """Host-store cells of the differential matrix: for every cache mode
    x W, generation with ``feature_store="host"`` — after the L3 gather
    lands and ``patch_batch`` fills the holes — produces feature rows
    bit-identical to the uncached oracle (the raw table), padded slots
    exactly zero, labels equal, zero drops, and a training loss equal
    bit-for-bit to the oracle batch's.  Recurring rngs prove the
    deferred-admission round warms the cache (hits appear by step 3
    without perturbing a single bit); the store's byte telemetry must
    account for the staging rounds."""
    out = run_forced(f"""
        MODE, W = {mode!r}, {w}
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig
        from repro.core.generation import make_distributed_generator
        from repro.core.host_store import empty_admit, patch_batch
        from repro.launch.mesh import make_mesh
        from repro.models import gcn as gcn_mod

        N, D, C = 600, 8, 7
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(N, avg_degree=8, n_hot=3, hot_degree=200, seed=0)
        part = partition_edges(g, W)
        X = node_features(N, D); Y = node_labels(N, C)
        table = balance_table(np.arange(N), W, seed=0)
        seeds = jnp.asarray(table.per_worker[:, :6])
        cc = None if MODE == "none" else CacheConfig(
            128, admit=1, assoc=2, mode=MODE,
            l1_rows=32 if MODE == "tiered" else 0, l1_promote=2)
        out = make_distributed_generator(mesh, part, X, Y, fanouts=(5, 3),
                                         cache_cfg=cc, feature_store="host")
        if cc is None:
            gen, dev, store = out
            cache = None
        else:
            gen, dev, store, cache = out
        patch = jax.jit(patch_batch)
        mcfg = dataclasses.replace(get_config("graphgen-gcn"), gcn_in_dim=D,
                                   gcn_hidden=16, n_classes=C, fanouts=(5, 3))
        params = gcn_mod.init_gcn(mcfg, jax.random.PRNGKey(1))
        loss_fn = jax.jit(gcn_mod.gcn_loss)
        adm = empty_admit(W, D)
        hits = 0
        for t in range(3):
            rng = jax.random.PRNGKey(t % 2)   # recurring ids warm the cache
            if cache is None:
                b, req = gen(dev, seeds, rng)
            else:
                b, cache, req = gen(dev, seeds, rng, cache, *adm)
            landed = store.issue(req.ids).rows()
            adm = (req.ids, landed)           # next step's deferred admission
            b = jax.tree.map(np.asarray, patch(b, req, landed))
            assert b.n_dropped.sum() == 0, b.n_dropped
            # --- bit-identical rows vs the uncached oracle (the table) ---
            np.testing.assert_array_equal(b.x_seed, X[b.seeds])
            oracle_hops = []
            for h, m, x in zip(b.hops, b.masks, b.x_hops):
                want = X[h] * m[..., None]          # padded slots exactly 0
                np.testing.assert_array_equal(x, want)
                oracle_hops.append(want)
            assert (b.labels == Y[b.seeds]).all()
            # --- bit-identical training loss vs the oracle batch ---------
            oracle = b._replace(x_seed=X[b.seeds],
                                x_hops=tuple(oracle_hops))
            l_got = np.asarray(loss_fn(params, jax.tree.map(jnp.asarray, b)))
            l_want = np.asarray(loss_fn(params,
                                        jax.tree.map(jnp.asarray, oracle)))
            assert l_got.tobytes() == l_want.tobytes(), (l_got, l_want)
            assert np.isfinite(l_got)
            hits += int(b.n_cache_hits.sum())
        if cc is not None:
            assert hits > 0, "deferred admission never warmed the cache"
        else:
            assert hits == 0
        assert store.bytes_issued > 0
        print("HOST_MATRIX_OK", MODE, W, hits)
    """, devices=w)
    assert "HOST_MATRIX_OK" in out


def test_host_fetch_conservation_empty_and_all_miss():
    """The L3 conservation contract at the fetch level on a W=4 mesh, in
    the two corners that break sloppy accounting: an ALL-MISS cold batch
    (every distinct id must surface as an L3 staging hit, or — when the
    staging buffer is deliberately undersized — as a counted miss AND a
    counted drop) and an EMPTY batch (all counters zero, while a pending
    landed buffer still gets admitted).  Every cell checks
    ``l1 + local + shard + l3 + misses == distinct`` per worker."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.feature_cache import CacheConfig, init_cache_state
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, d, R = 4, 3, 24
        mesh = make_mesh((W,), ("data",))
        spec = NamedSharding(mesh, P("data"))

        def make_run(cfg, capacity, r):
            def worker(i, cc, aid, arows):
                cc = jax.tree.map(lambda a: a[0], cc)
                out, cc, fs, cs, req = fetch_rows(
                    None, i[0], "data", capacity=capacity, cache=cc,
                    cache_cfg=cfg, store="host", feat_dim=d,
                    host_admit=(aid[0], arows[0]))
                pack = lambda t: jax.tree.map(lambda a: a[None], t)
                return out[None], pack(cc), pack((fs, cs)), pack(req)
            return jax.jit(shard_map(
                worker, mesh=mesh,
                in_specs=(P("data"),) * 4, out_specs=(P("data"),) * 4,
                check_vma=False))

        for mode in ("replicated", "sharded", "tiered"):
            cfg = CacheConfig(32, admit=1, assoc=2, mode=mode,
                              l1_rows=16 if mode == "tiered" else 0,
                              l1_promote=2, store="host").validated()
            # distinct per-worker ids, cold cache: all-miss
            ids = np.stack([np.arange(R) + 100 * k for k in range(W)]
                           ).astype(np.int32)
            no_admit = (jnp.full((W, 1), -1, jnp.int32),
                        jnp.zeros((W, 1, d), jnp.float32))

            def conserve(cs, distinct):
                l1 = np.asarray(cs.n_l1_hits); loc = np.asarray(cs.n_local_hits)
                sh = np.asarray(cs.n_shard_hits); l3 = np.asarray(cs.n_l3_hits)
                ms = np.asarray(cs.n_misses)
                assert (l1 + loc + sh + l3 + ms == distinct).all(), \\
                    (mode, l1, loc, sh, l3, ms, distinct)
                return l3, ms

            # ample staging: every distinct id is an L3 hit, zero drops
            run = make_run(cfg, 2 * R, R)
            state = jax.device_put(init_cache_state(cfg, d, W), spec)
            out, state, (fs, cs), req = run(
                jnp.asarray(ids), state, *[jax.device_put(a, spec)
                                           for a in no_admit])
            l3, ms = conserve(cs, R)
            assert (l3 == R).all() and (ms == 0).all()
            assert int(np.asarray(fs.n_dropped).sum()) == 0
            assert (np.asarray(req.ids) >= 0).sum() == W * R
            assert int(np.asarray(fs.host_gather_bytes).sum()) > 0

            # undersized staging: the overflow is COUNTED miss + drop
            cap = 4
            run = make_run(cfg, cap, R)
            state = jax.device_put(init_cache_state(cfg, d, W), spec)
            out, state, (fs, cs), req = run(
                jnp.asarray(ids), state, *[jax.device_put(a, spec)
                                           for a in no_admit])
            l3, ms = conserve(cs, R)
            assert (l3 == cap).all() and (ms == R - cap).all()
            assert (np.asarray(fs.n_dropped) == R - cap).all()

            # empty batch: all counters zero, deferred admission still runs
            run = make_run(cfg, 4, 0)
            state = jax.device_put(init_cache_state(cfg, d, W), spec)
            admit = (jnp.asarray(np.stack(
                         [[7 + k, -1] for k in range(W)]).astype(np.int32)),
                     jnp.ones((W, 2, d), jnp.float32))
            out, state, (fs, cs), req = run(
                jnp.zeros((W, 0), jnp.int32), state,
                *[jax.device_put(a, spec) for a in admit])
            l3, ms = conserve(cs, 0)
            assert out.shape == (W, 0, d)
            assert int(np.asarray(fs.n_dropped).sum()) == 0
            assert int(np.asarray(cs.n_inserted).sum()) >= W, \\
                "pending landed rows were not admitted on the empty step"
        print("L3_CONSERVATION_OK")
    """, devices=4)
    assert "L3_CONSERVATION_OK" in out


def test_cached_fetch_all_modes_bit_identical_w4():
    """Fetch-level complement of the matrix on one W=4 mesh: random request
    mixes against every (mode, assoc) cell return rows bit-identical to
    the raw table with zero drops, the hit split stays consistent
    (l1 + local + shard == hits), and the conservation invariant
    l1 + local + shard + misses == distinct holds per worker."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.feature_cache import CacheConfig, init_cache_state
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, rows_pw, d = 4, 32, 3
        mesh = make_mesh((W,), ("data",))
        table = np.arange(W * rows_pw * d,
                          dtype=np.float32).reshape(W * rows_pw, d)
        spec = NamedSharding(mesh, P("data"))
        cells = [("replicated", 1), ("replicated", 4), ("sharded", 1),
                 ("sharded", 2), ("sharded", 4), ("tiered", 1),
                 ("tiered", 2), ("tiered", 4)]
        for trial, (mode, assoc) in enumerate(cells):
            cfg = CacheConfig(32, admit=1, assoc=assoc, mode=mode,
                              l1_rows=16 if mode == "tiered" else 0,
                              l1_promote=2).validated()

            def worker(t, i, cc):
                cc = jax.tree.map(lambda a: a[0], cc)
                out, cc, fs, cs = fetch_rows(t, i[0], "data", cache=cc,
                                             cache_cfg=cfg)
                return (out[None], jax.tree.map(lambda a: a[None], cc),
                        jax.tree.map(lambda a: a[None], (fs, cs)))

            run = jax.jit(shard_map(
                worker, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data")),
                out_specs=(P("data"), P("data"), P("data")),
                check_vma=False))
            state = jax.device_put(init_cache_state(cfg, d, W), spec)
            rng = np.random.default_rng(trial)
            total_hits = total_l1 = 0
            for it in range(6):
                ids = rng.integers(0, W * rows_pw, (W, 48)).astype(np.int32)
                out, state, (fs, cs) = run(
                    jnp.asarray(table), jax.device_put(jnp.asarray(ids), spec),
                    state)
                np.testing.assert_array_equal(
                    np.asarray(out).reshape(W, 48, d),
                    table[ids])
                assert int(np.asarray(fs.n_dropped).sum()) == 0
                l1 = np.asarray(cs.n_l1_hits)
                loc = np.asarray(cs.n_local_hits)
                sh = np.asarray(cs.n_shard_hits)
                ms = np.asarray(cs.n_misses)
                assert (l1 + loc + sh == np.asarray(cs.n_hits)).all()
                distinct = np.asarray(
                    [len(np.unique(ids[k])) for k in range(W)])
                assert (l1 + loc + sh + ms == distinct).all(), (mode, assoc)
                if mode != "tiered":
                    assert (l1 == 0).all()
                total_hits += int(np.asarray(cs.n_hits).sum())
                total_l1 += int(l1.sum())
            assert total_hits > 0, (mode, assoc)
            if mode == "tiered":
                assert total_l1 > 0, "L1 never promoted"
        print("ALL_MODES_FETCH_OK")
    """, devices=4)
    assert "ALL_MODES_FETCH_OK" in out


def test_sharded_cache_beats_replicated_capacity():
    """The reason sharding exists: at equal per-worker cache_rows over a
    shared hot set larger than one replica, the W-sharded cache serves
    strictly more unique hits (effective capacity x W) AND a remote-shard
    hit population appears."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.feature_cache import CacheConfig, init_worker_caches
        from repro.core.generation import fetch_rows
        from repro.launch.mesh import make_mesh

        W, rows_pw, d, c = 4, 64, 2, 32
        mesh = make_mesh((W,), ("data",))
        table = np.arange(W * rows_pw * d,
                          dtype=np.float32).reshape(W * rows_pw, d)
        spec = NamedSharding(mesh, P("data"))
        rng = np.random.default_rng(0)
        # a hot set of ~3*c ids: one 32-row replica can never hold it, the
        # 4 x 32 sharded aggregate can
        hot = rng.choice(W * rows_pw, size=3 * c, replace=False)
        streams = [np.stack([rng.choice(hot, size=96) for _ in range(W)])
                   .astype(np.int32) for _ in range(10)]

        def run_mode(mode):
            cfg = CacheConfig(c, admit=1, assoc=2, mode=mode)

            def worker(t, i, cc):
                cc = jax.tree.map(lambda a: a[0], cc)
                out, cc, fs, cs = fetch_rows(t, i[0], "data", cache=cc,
                                             cache_cfg=cfg)
                return (out[None], jax.tree.map(lambda a: a[None], cc),
                        jax.tree.map(lambda a: a[None], (fs, cs)))

            run = jax.jit(shard_map(
                worker, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data")),
                out_specs=(P("data"), P("data"), P("data")),
                check_vma=False))
            state = jax.device_put(init_worker_caches(c, d, W), spec)
            hits = shard_hits = 0
            for ids in streams:
                out, state, (fs, cs) = run(
                    jnp.asarray(table),
                    jax.device_put(jnp.asarray(ids), spec), state)
                np.testing.assert_array_equal(
                    np.asarray(out).reshape(W, 96, d), table[ids])
                hits += int(np.asarray(cs.n_hits).sum())
                shard_hits += int(np.asarray(cs.n_shard_hits).sum())
            return hits, shard_hits

        rep_hits, rep_shard = run_mode("replicated")
        sh_hits, sh_shard = run_mode("sharded")
        assert rep_shard == 0
        assert sh_shard > 0
        assert sh_hits > rep_hits, (sh_hits, rep_hits)
        print("SHARDED_CAPACITY_OK", rep_hits, sh_hits)
    """, devices=4)
    assert "SHARDED_CAPACITY_OK" in out


def test_tiered_cached_generation_multiworker_warms_l1():
    """End-to-end: the full generation engine with the TIERED cache on 8
    workers — the rows stay bit-identical to the uncached generator under
    the same rng (the matrix covers the sweep; this pins the 8-worker
    scale), the hit rate climbs on recurring ids, AND a promoted-L1 hit
    population appears, serving part of the stream with zero network."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh

        W = 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(2000, avg_degree=8, n_hot=3, hot_degree=500, seed=0)
        part = partition_edges(g, W)
        X = node_features(2000, 16); Y = node_labels(2000, 7)
        table = balance_table(np.arange(2000), W, seed=0)
        seeds = jnp.asarray(table.per_worker[:, :16])
        gen_nc, dev_nc = make_distributed_generator(mesh, part, X, Y,
                                                    fanouts=(8, 4))
        gen_c, dev_c, cache = make_distributed_generator(
            mesh, part, X, Y, fanouts=(8, 4),
            cache_cfg=CacheConfig(256, admit=1, assoc=2, mode="tiered",
                                  l1_rows=64, l1_promote=2))
        hit_rates = []
        for t in range(5):
            rng = jax.random.PRNGKey(t % 2)   # recurring rngs -> recurring ids
            b_nc = gen_nc(dev_nc, seeds, rng)
            b_c, cache = gen_c(dev_c, seeds, rng, cache)
            np.testing.assert_array_equal(np.asarray(b_nc.x_seed),
                                          np.asarray(b_c.x_seed))
            for a, b in zip(b_nc.x_hops, b_c.x_hops):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert (np.asarray(b_c.labels) == np.asarray(b_nc.labels)).all()
            assert np.asarray(b_c.n_dropped).sum() == 0
            hits = np.asarray(b_c.n_cache_hits).sum()
            total = hits + np.asarray(b_c.n_cache_misses).sum()
            hit_rates.append(hits / total)
        assert hit_rates[0] == 0.0                   # cold cache
        assert hit_rates[-1] > 0.5, hit_rates        # recurring ids now cached
        # the promoted head is resident in (at least one) L1 replica
        assert int(np.asarray(cache.l1.keys >= 0).sum()) > 0
        print("TIERED_GEN_OK", [round(h, 3) for h in hit_rates])
    """)
    assert "TIERED_GEN_OK" in out


def test_generation_three_hop_multiworker():
    """The depth-3 engine on 8 workers: chained masks, valid neighbors,
    correct features at every level."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh

        W = 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(1500, avg_degree=8, n_hot=3, hot_degree=300, seed=1)
        part = partition_edges(g, W)
        X = node_features(1500, 8); Y = node_labels(1500, 5)
        table = balance_table(np.arange(1500), W, seed=0)
        seeds = table.per_worker[:, :8]
        gen, dev = make_distributed_generator(mesh, part, X, Y,
                                              fanouts=(5, 4, 3))
        b = jax.tree.map(np.asarray,
                         gen(dev, jnp.asarray(seeds), jax.random.PRNGKey(0)))
        assert [h.shape[1:] for h in b.hops] == [(5,), (5, 4), (5, 4, 3)]
        adj = {v: set(g.indices[g.indptr[v]:g.indptr[v+1]]) for v in range(1500)}
        for i, s in enumerate(b.seeds):
            for j in range(5):
                if b.masks[0][i, j]:
                    assert b.hops[0][i, j] in adj[s]
        for l in range(1, 3):
            assert not (b.masks[l] & ~b.masks[l-1][..., None]).any()
            ml = b.masks[l]
            if ml.any():
                assert np.abs(b.x_hops[l][ml] - X[b.hops[l][ml]]).max() == 0
            if (~ml).any():
                assert np.abs(b.x_hops[l][~ml]).max() == 0
        assert (b.labels == Y[b.seeds]).all()
        assert b.n_dropped.shape == (W,)
        print("THREE_HOP_OK")
    """)
    assert "THREE_HOP_OK" in out


def test_calibration_probes_cached_generator_cold():
    """The slack ladder probes the CONFIGURED (cached) generator with a
    cold cache per rung, and the chosen slack is drop-free from cold —
    a rung warmed by its predecessor would understate cold-start miss
    traffic and pick a slack that drops on the real run's first steps."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig, init_worker_caches
        from repro.core.generation import (make_distributed_generator,
                                           make_generator_fn)
        from repro.core.partition import partition_edges
        from repro.launch.mesh import make_mesh
        from repro.launch.train import calibrate_capacity_slack

        W, n, dim = 4, 2000, 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(n, avg_degree=8, n_hot=3, hot_degree=400, seed=0)
        part = partition_edges(g, W)
        X = node_features(n, dim); Y = node_labels(n, 5)
        table = balance_table(np.arange(n), W, seed=0)
        cfg = CacheConfig(256, admit=2, assoc=2, mode="sharded")
        _, dev = make_distributed_generator(mesh, part, X, Y, fanouts=(6, 4))
        probes = [(jnp.asarray(table.per_worker[:, t*8:(t+1)*8]),
                   jax.random.PRNGKey(t)) for t in range(2)]
        slack = calibrate_capacity_slack(mesh, dev, (6, 4), probes,
                                         cache_cfg=cfg)
        assert slack in (0.25, 0.5, 1.0, 1.5, 2.0), slack
        # the chosen slack must be drop-free from a COLD cache
        gen = jax.jit(make_generator_fn(mesh, fanouts=(6, 4),
                                        capacity_slack=slack, cache_cfg=cfg))
        cache = jax.device_put(init_worker_caches(256, dim, W),
                               NamedSharding(mesh, P("data")))
        for seeds, rng in probes:
            batch, cache = gen(dev, seeds, rng, cache)
            assert int(np.asarray(batch.n_dropped).sum()) == 0
        print("CALIBRATION_COLD_OK", slack)
    """, devices=4)
    assert "CALIBRATION_COLD_OK" in out


def test_hit_cap_calibration_ladder_and_dense_fallback():
    """The compact-wire calibration: the ladder returns a compact config
    whose hit_cap demotes nothing on the probes (re-checked from cold),
    and a ladder whose every rung demotes falls back to the dense wire —
    the rung that can never demote."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig, init_cache_state
        from repro.core.generation import (make_distributed_generator,
                                           make_generator_fn)
        from repro.core.partition import partition_edges
        from repro.launch.mesh import make_mesh
        from repro.launch.train import calibrate_probe_hit_cap

        W, n, dim = 4, 2000, 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(n, avg_degree=8, n_hot=3, hot_degree=400, seed=0)
        part = partition_edges(g, W)
        X = node_features(n, dim); Y = node_labels(n, 5)
        table = balance_table(np.arange(n), W, seed=0)
        cfg = CacheConfig(256, admit=1, assoc=2, mode="sharded",
                          wire="compact")
        _, dev = make_distributed_generator(mesh, part, X, Y, fanouts=(6, 4))
        # recurring seeds across probes: the cache warms and the probe
        # round produces real hits for the ladder to bound
        probes = [(jnp.asarray(table.per_worker[:, :8]),
                   jax.random.PRNGKey(0)) for _ in range(3)]
        cal = calibrate_probe_hit_cap(mesh, dev, (6, 4), probes, 2.0, cfg)
        assert cal.wire == "compact" and cal.hit_cap > 0, cal
        # the calibrated config demotes nothing from a cold start
        gen = jax.jit(make_generator_fn(mesh, fanouts=(6, 4),
                                        capacity_slack=2.0, cache_cfg=cal))
        cache = jax.device_put(init_cache_state(cal, dim, W),
                               NamedSharding(mesh, P("data")))
        for seeds, rng in probes:
            batch, cache = gen(dev, seeds, rng, cache)
            assert int(np.asarray(batch.n_probe_demoted).sum()) == 0
            assert int(np.asarray(batch.n_dropped).sum()) == 0
        # a ladder whose only rung is ~zero must demote and fall back
        dense = calibrate_probe_hit_cap(mesh, dev, (6, 4), probes, 2.0,
                                        cfg, ladder=(0.0001,))
        assert dense.wire == "dense" and dense.hit_cap == 0, dense
        print("HIT_CAP_CAL_OK", cal.hit_cap)
    """, devices=4)
    assert "HIT_CAP_CAL_OK" in out


def test_elastic_checkpoint_reshard():
    """Save on 4 workers, restore on 2 (node loss) — values identical."""
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.train import checkpoint as ckpt

        d = tempfile.mkdtemp()
        mesh4 = make_mesh((4,), ("data",))
        tree = {"w": jax.device_put(jnp.arange(64.).reshape(8, 8),
                                    NamedSharding(mesh4, P("data", None))),
                "b": jnp.ones((3,))}
        ckpt.save(d, 7, tree)
        mesh2 = make_mesh((2,), ("data",))
        shards = {"w": NamedSharding(mesh2, P("data", None)),
                  "b": NamedSharding(mesh2, P())}
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        restored = ckpt.restore(d, 7, like, shardings=shards)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.).reshape(8, 8))
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_grad_sync_tree_equals_default():
    out = run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train.train_loop import make_shardmap_grad_sync

        mesh = make_mesh((8,), ("data",))
        grads = {"a": jnp.arange(24.).reshape(8, 3), "b": jnp.ones((8, 2))}
        sync = make_shardmap_grad_sync(mesh)
        out = sync(grads)
        # replicated input: sum of 8 copies / 8 == identity
        np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(grads["a"]))
        np.testing.assert_allclose(np.asarray(out["b"]), np.asarray(grads["b"]))
        print("SYNC_OK")
    """)
    assert "SYNC_OK" in out


#: the serving extension of the matrix: the frozen (read-mostly) serve
#: generator — the forward-only form the GraphServer compiles — must
#: produce batches and GCN forward logits bit-identical to the uncached
#: oracle, while serving real hits from the state warmed by the mutable
#: generator
SERVE_MODES = ("replicated", "sharded", "tiered")


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("mode", SERVE_MODES)
def test_serve_frozen_differential_cells(mode, w):
    """The serving contract, per mode x W cell: warm a cache with the
    mutable training generator, freeze it (serve_view), and check the
    forward-only serve generator's batch is bit-identical to the
    uncached oracle (rows from the raw table, padded slots exactly
    zero, labels match) AND the GCN forward logits — what serve()
    argmaxes — are bit-identical to the oracle batch's.  The frozen
    cells must also serve warm hits: a serve path that never hits
    would pass bit-identity trivially by fetching everything."""
    out = run_forced(f"""
        MODE, W = {mode!r}, {w}
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.balance import balance_table
        from repro.core.feature_cache import CacheConfig
        from repro.core.generation import (make_distributed_generator,
                                           make_generator_fn)
        from repro.launch.mesh import make_mesh
        from repro.models import gcn as gcn_mod

        N, D, C = 600, 8, 7
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(N, avg_degree=8, n_hot=3, hot_degree=200, seed=0)
        part = partition_edges(g, W)
        X = node_features(N, D); Y = node_labels(N, C)
        table = balance_table(np.arange(N), W, seed=0)
        seeds = jnp.asarray(table.per_worker[:, :6])
        cc = CacheConfig(128, admit=1, assoc=2, mode=MODE,
                         l1_rows=32 if MODE == "tiered" else 0, l1_promote=2)
        gen_mut, dev, cache = make_distributed_generator(
            mesh, part, X, Y, fanouts=(5, 3), cache_cfg=cc)
        # warm on the ids the serve requests will replay
        for t in range(3):
            _, cache = gen_mut(dev, seeds, jax.random.PRNGKey(t % 2), cache)
        gen_frozen = jax.jit(make_generator_fn(
            mesh, fanouts=(5, 3), cache_cfg=cc.serve_view()))
        mcfg = dataclasses.replace(get_config("graphgen-gcn"), gcn_in_dim=D,
                                   gcn_hidden=16, n_classes=C, fanouts=(5, 3))
        params = gcn_mod.init_gcn(mcfg, jax.random.PRNGKey(1))
        fwd = jax.jit(gcn_mod.gcn_forward)
        hits = 0
        for t in range(3):
            rng = jax.random.PRNGKey(t % 2)   # replay the warmed ids
            b = jax.tree.map(np.asarray, gen_frozen(dev, seeds, rng, cache))
            assert b.n_dropped.sum() == 0, b.n_dropped
            np.testing.assert_array_equal(b.x_seed, X[b.seeds])
            oracle_hops = []
            for h, m, x in zip(b.hops, b.masks, b.x_hops):
                want = X[h] * m[..., None]        # padded slots exactly 0
                np.testing.assert_array_equal(x, want)
                oracle_hops.append(want)
            assert (b.labels == Y[b.seeds]).all()
            oracle = b._replace(x_seed=X[b.seeds], x_hops=tuple(oracle_hops))
            l_got = np.asarray(fwd(params, jax.tree.map(jnp.asarray, b)))
            l_want = np.asarray(fwd(params, jax.tree.map(jnp.asarray, oracle)))
            assert l_got.tobytes() == l_want.tobytes()
            hits += int(b.n_cache_hits.sum())
        assert hits > 0, "frozen serve cells must hit the warmed state"
        print("SERVE_OK", MODE, W, hits)
    """, devices=w)
    assert "SERVE_OK" in out
