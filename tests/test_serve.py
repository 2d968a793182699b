"""Serving-tier units: the bucket ladder, the GraphServer request path
(zero recompiles after warmup), the frozen-cache bit-stability contract,
the serving-checkpoint round trip, and the ``--prompt-len 0`` LM decode
regression.  Multi-worker serve cells (the frozen differential matrix)
run in test_distributed.py subprocesses."""
import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.feature_cache import CacheConfig, init_cache_state
from repro.core.generation import fetch_rows, make_distributed_generator
from repro.core.partition import partition_edges
from repro.graph.synthetic import node_features, node_labels, powerlaw_graph
from repro.launch.mesh import make_mesh
from repro.launch.serve import GraphServer, bucket_for, serve_lm
from repro.models import gcn as gcn_mod
from repro.train import checkpoint as ckpt


# ------------------------------------------------------------- bucket ladder

def test_bucket_for_picks_smallest_covering_bucket():
    """The ladder maps a request to the smallest bucket whose padded
    capacity (bucket x workers) holds it — minimal pad waste."""
    assert bucket_for(1, (8, 16, 32), 1) == 8
    assert bucket_for(8, (8, 16, 32), 1) == 8
    assert bucket_for(9, (8, 16, 32), 1) == 16
    assert bucket_for(32, (8, 16, 32), 1) == 32
    # capacity is per-worker slots x workers
    assert bucket_for(30, (8, 16, 32), 4) == 8
    assert bucket_for(33, (8, 16, 32), 4) == 16


def test_bucket_for_rejects_oversize_and_empty():
    """Oversized requests raise (split, never silently truncate); empty
    requests raise (nothing to predict)."""
    with pytest.raises(ValueError, match="exceeds"):
        bucket_for(33, (8, 16, 32), 1)
    with pytest.raises(ValueError, match="at least one seed"):
        bucket_for(0, (8, 16, 32), 1)


# -------------------------------------------------------- GraphServer (W=1)

def _tiny_serving_stack(cached: bool):
    """A W=1 serving stack on a small power-law graph: (server, n_nodes).
    ``cached=False`` keeps the single-device unit cheap; the cached cells
    run in the test_distributed.py matrix."""
    N, D, C = 200, 6, 5
    mesh = make_mesh((1,), ("data",))
    g = powerlaw_graph(N, avg_degree=6, n_hot=3, hot_degree=50, seed=0)
    part = partition_edges(g, 1)
    X, Y = node_features(N, D), node_labels(N, C)
    cc = CacheConfig(64, admit=1, assoc=2) if cached else None
    out = make_distributed_generator(mesh, part, X, Y, fanouts=(4, 3),
                                     cache_cfg=cc)
    mcfg = dataclasses.replace(get_config("graphgen-gcn"), gcn_in_dim=D,
                               gcn_hidden=8, n_classes=C, fanouts=(4, 3))
    params = gcn_mod.init_gcn(mcfg, jax.random.PRNGKey(1))
    server = GraphServer(out[0], out[1], params, None,
                         logits=gcn_mod.gcn_forward, buckets=(4, 8),
                         n_workers=1)
    return server, N


def test_graph_server_compiles_ladder_once_then_never_again():
    """THE serving invariant: warmup compiles exactly one program per
    bucket; every later request — any size the ladder covers — lands on
    a compiled program (compile count frozen)."""
    server, n_nodes = _tiny_serving_stack(cached=False)
    assert server.warmup() == len(server.buckets) == 2
    rng = np.random.default_rng(0)
    for size in (1, 3, 4, 5, 8):
        preds = server.serve(rng.integers(0, n_nodes, size))
        assert preds.shape == (size,)
        assert preds.dtype == np.int32
    assert server.compile_count() == len(server.buckets), \
        "a request traced a new program — the zero-recompile gate"


def test_graph_server_rejects_oversize_request():
    """A request beyond the ladder's capacity raises — it must be split
    by the caller, never padded to a shape that was never compiled."""
    server, _ = _tiny_serving_stack(cached=False)
    with pytest.raises(ValueError, match="exceeds"):
        server.serve(np.zeros(server.capacity + 1, np.int32))


def test_graph_server_is_deterministic_per_request_index():
    """Serving is reproducible: two fresh same-seed servers answer the
    same request stream with bit-identical predictions (the per-request
    rng is fold_in(seed rng, request index), never wall clock or global
    state).  The returned slice also never exposes pad-slot predictions."""
    server_a, n_nodes = _tiny_serving_stack(cached=False)
    server_b, _ = _tiny_serving_stack(cached=False)
    rng = np.random.default_rng(3)
    for size in (3, 8, 5):
        ids = rng.integers(0, n_nodes, size)
        pa, pb = server_a.serve(ids), server_b.serve(ids)
        np.testing.assert_array_equal(pa, pb)
        assert pa.shape == (size,)


# -------------------------------------------- frozen-cache read-only contract

@pytest.mark.parametrize("mode", ["replicated", "tiered"])
def test_frozen_fetch_cache_state_bit_stable(mode):
    """The read-mostly contract at the fetch level: a warmed state run
    under the frozen serve view returns (1) the exact table rows and
    (2) a cache state whose every leaf is BIT-identical to the input —
    no admission, no counter bumps, no L1 promotion — while still
    serving hits from the warm slots."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rows_n, d = 64, 4
    mesh = make_mesh((1,), ("data",))
    cfg = CacheConfig(32, admit=1, assoc=2, mode=mode,
                      l1_rows=16 if mode == "tiered" else 0,
                      l1_promote=2).validated()
    table = jnp.asarray(
        np.random.default_rng(0).normal(size=(rows_n, d)).astype(np.float32))
    state = jax.tree.map(jnp.asarray, init_cache_state(cfg, d, 1))
    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, rows_n, (1, 24)).astype(np.int32))

    def make_run(run_cfg):
        def worker(t, i, c):
            c = jax.tree.map(lambda a: a[0], c)
            out, c, fs, cs = fetch_rows(t, i[0], "data", cache=c,
                                        cache_cfg=run_cfg)
            return (out[None], jax.tree.map(lambda a: a[None], c),
                    jax.tree.map(lambda a: a[None], (fs, cs)))
        return jax.jit(shard_map(
            worker, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")),
            check_vma=False))

    # warm under the MUTABLE config (repeat ids so admit=1 + promotion fire)
    run_mut = make_run(cfg)
    for _ in range(3):
        _, state, _ = run_mut(table, ids, state)

    run_frozen = make_run(cfg.serve_view())
    before = jax.tree.map(np.asarray, state)
    total_hits = 0
    for _ in range(3):
        out, state, (fs, cs) = run_frozen(table, ids, state)
        np.testing.assert_array_equal(np.asarray(out)[0],
                                      np.asarray(table)[np.asarray(ids)[0]])
        assert int(np.asarray(fs.n_dropped).sum()) == 0
        total_hits += int(np.asarray(cs.n_hits).sum())
    after = jax.tree.map(np.asarray, state)
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert x.tobytes() == y.tobytes(), \
            "a frozen fetch mutated the cache state"
    assert total_hits > 0, "frozen probes must serve the warm slots"


def test_serve_view_freezes_and_forces_device_store():
    """serve_view() keeps the slot layout (same probe addressing as the
    warmed state) but flips frozen=True and store='device'; a frozen
    config with a host store is rejected outright."""
    cfg = CacheConfig(128, admit=2, assoc=4, mode="tiered", l1_rows=32,
                      store="host").validated()
    sv = cfg.serve_view()
    assert sv.frozen and sv.store == "device"
    assert (sv.n_rows, sv.assoc, sv.mode, sv.l1_rows) == \
        (cfg.n_rows, cfg.assoc, cfg.mode, cfg.l1_rows)
    with pytest.raises(ValueError, match="frozen"):
        CacheConfig(128, frozen=True, store="host").validated()


# ------------------------------------------------------- serving checkpoints

def test_serving_checkpoint_round_trip_bit_exact(tmp_path):
    """save_serving_state/restore_serving_state round-trips params and
    the warm cache bit-exactly, and the latest step is selected."""
    cfg = CacheConfig(32, admit=1, assoc=2).validated()
    rng = np.random.default_rng(0)
    params = {"w1": rng.normal(size=(4, 3)).astype(np.float32),
              "b1": rng.normal(size=(3,)).astype(np.float32)}
    cache = init_cache_state(cfg, 3, 1)
    cache.keys[0, :5] = np.arange(5)            # a few warm slots
    cache.rows[0, :5] = rng.normal(size=(5, 3)).astype(np.float32)
    ckpt.save_serving_state(str(tmp_path), 7, params, cache, cache_cfg=cfg)
    p2, c2 = ckpt.restore_serving_state(
        str(tmp_path), jax.tree.map(jnp.asarray, params),
        jax.tree.map(jnp.asarray, cache), expect_cache_cfg=cfg.serve_view())
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(c2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_serving_checkpoint_rejects_layout_mismatch(tmp_path):
    """A cache state only probes correctly under the layout it was warmed
    with — restoring under a different n_rows/assoc must raise, not
    silently probe cold."""
    cfg = CacheConfig(32, admit=1, assoc=2).validated()
    cache = init_cache_state(cfg, 3, 1)
    ckpt.save_serving_state(str(tmp_path), 1, {"w": np.zeros(2, np.float32)},
                            cache, cache_cfg=cfg)
    other = CacheConfig(64, admit=1, assoc=2).validated()
    with pytest.raises(ValueError, match="layout mismatch"):
        ckpt.restore_serving_state(
            str(tmp_path), {"w": np.zeros(2, np.float32)}, cache,
            expect_cache_cfg=other)
    with pytest.raises(FileNotFoundError):
        ckpt.restore_serving_state(
            str(tmp_path / "empty"), {"w": np.zeros(2, np.float32)}, cache)


# -------------------------------------------------------- LM decode driver

def _lm_args(**over):
    base = dict(arch="smollm-135m", smoke=True, seed=0, batch=2,
                prompt_len=4, gen_len=3)
    base.update(over)
    return argparse.Namespace(**base)


@pytest.mark.parametrize("compiles", [0, 2])
def test_serve_main_fails_on_request_path_compiles(monkeypatch, compiles):
    """A request that compiles on the request path fails the run."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(serve, "serve_gcn",
                        lambda args: {"request_path_compiles": compiles})
    monkeypatch.setattr(sys, "argv", ["serve", "--arch", "graphgen-gcn"])
    if not compiles:
        serve.main()
        return
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code and "request path" in str(exc.value.code)


def test_serve_lm_prompt_len_zero_regression():
    """--prompt-len 0 must serve, not crash: the prefill loop is
    zero-trip, so there are no prompt logits — generation starts from
    the fixed BOS-like token (the old driver hit NameError: logits)."""
    rec = serve_lm(_lm_args(prompt_len=0))
    assert rec["tokens"].shape == (2, 3)
    assert rec["tok_s"] > 0


def test_serve_lm_returns_all_generated_tokens():
    """The timed loop accumulates device arrays (no per-token host sync)
    and still returns every generated token, in order, on host."""
    rec = serve_lm(_lm_args())
    assert rec["tokens"].shape == (2, 3)
    assert rec["tokens"].dtype == np.int32


def test_serve_lm_zero_gen_len_returns_empty():
    """gen-len 0: nothing generated, empty (batch, 0) token array, no
    division-by-zero or empty-concatenate crash."""
    rec = serve_lm(_lm_args(gen_len=0))
    assert rec["tokens"].shape == (2, 0)
