"""Correctness of the §Perf optimization variants: every optimized path
must be numerically equivalent to its baseline."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_forced(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    prologue = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
    )
    proc = subprocess.run([sys.executable, "-c", prologue + textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_chunked_attention_matches_naive():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 1024, 4, 32))
    k = jax.random.normal(ks[1], (2, 1024, 2, 32))
    v = jax.random.normal(ks[2], (2, 1024, 2, 32))
    naive = L.gqa_attention(q, k, v, causal=True)
    for blk in (128, 256, 512):
        chunk = L.chunked_attention(q, k, v, causal=True, block=blk)
        np.testing.assert_allclose(np.asarray(chunk), np.asarray(naive),
                                   rtol=2e-3, atol=2e-3)


def test_chunked_attention_grad_finite():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 512, 2, 16))
    k = jax.random.normal(ks[1], (1, 512, 2, 16))
    v = jax.random.normal(ks[2], (1, 512, 2, 16))
    g = jax.grad(lambda q: L.chunked_attention(q, k, v, True, 128).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_moe_ep_a2a_matches_dense_mixture():
    out = _run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L, moe
        from repro.configs import REGISTRY, smoke_config
        mesh = make_mesh((2, 4), ("data", "model"))
        L.set_mesh(mesh)
        cfg = smoke_config(REGISTRY["qwen3-moe-30b-a3b"])
        p = jax.tree.map(lambda a: a[0], moe.init_moe_mlp(jax.random.PRNGKey(0), cfg, 1))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)
        def dense_ref(p, x):
            t = x.reshape(-1, 64)
            pr = jax.nn.softmax((t @ p["router"]).astype(jnp.float32), -1)
            topv, topi = jax.lax.top_k(pr, cfg.top_k)
            topv = topv / topv.sum(-1, keepdims=True)
            oe = jnp.stack([(jax.nn.silu(t@p["wg"][e]) * (t@p["wu"][e])) @ p["wd"][e]
                            for e in range(cfg.n_experts)], 1)
            w = jnp.zeros((t.shape[0], cfg.n_experts)).at[
                jnp.arange(t.shape[0])[:, None], topi].set(topv)
            return jnp.einsum("te,ted->td", w, oe).reshape(x.shape)
        want = dense_ref(p, x)
        moe.set_moe_impl("ep_a2a")
        got = jax.jit(lambda p, x: moe.moe_forward(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
        g = jax.grad(lambda x: jnp.sum(
            jax.jit(lambda p, x: moe.moe_forward(p, x, cfg))(p, x) ** 2))(x)
        assert np.isfinite(np.asarray(g)).all()
        print("EP_OK")
    """)
    assert "EP_OK" in out


def test_reduce_scatter_generation_matches_butterfly():
    out = _run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.graph.synthetic import powerlaw_graph, node_features, node_labels
        from repro.core.partition import partition_edges
        from repro.core.generation import make_distributed_generator
        from repro.launch.mesh import make_mesh
        W = 8
        mesh = make_mesh((W,), ("data",))
        g = powerlaw_graph(2000, avg_degree=8, n_hot=3, hot_degree=500, seed=0)
        part = partition_edges(g, W)
        X = node_features(2000, 16); Y = node_labels(2000, 7)
        seeds = np.arange(W * 16, dtype=np.int32).reshape(W, 16)
        gb, db = make_distributed_generator(mesh, part, X, Y, fanouts=(8, 4))
        gr, dr = make_distributed_generator(mesh, part, X, Y, fanouts=(8, 4),
                                            merge_mode="reduce_scatter")
        bb = jax.tree.map(np.asarray, gb(db, jnp.asarray(seeds), jax.random.PRNGKey(3)))
        br = jax.tree.map(np.asarray, gr(dr, jnp.asarray(seeds), jax.random.PRNGKey(3)))
        # identical candidate multisets -> identical min-k per frontier row
        np.testing.assert_array_equal(np.sort(bb.hop1, -1), np.sort(br.hop1, -1))
        np.testing.assert_array_equal(bb.mask1, br.mask1)
        adj = {v: set(g.indices[g.indptr[v]:g.indptr[v+1]]) for v in range(2000)}
        for i in range(br.hop1.shape[0]):
            for j in range(8):
                if br.mask1[i, j]:
                    assert br.hop1[i, j] in adj[br.seeds[i]]
        assert np.abs(br.x_hop1[br.mask1] - X[br.hop1[br.mask1]]).max() == 0
        print("RS_OK")
    """)
    assert "RS_OK" in out


def test_tree_reduce_scatter_segments():
    out = _run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.core.tree_reduce import tree_reduce_scatter
        from repro.launch.mesh import make_mesh
        W, F = 8, 32
        mesh = make_mesh((W,), ("data",))
        # per-worker data [F]: value = worker_id; merge = add
        x = jnp.tile(jnp.arange(W, dtype=jnp.float32)[:, None], (1, F))
        def body(v):
            return tree_reduce_scatter(
                v[0], lambda a, b: a + b, "data")
        out = shard_map(body, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)(x)
        # every row of every segment = sum over workers = 28
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((W * (F // W),), 28.0))
        print("SEG_OK")
    """)
    assert "SEG_OK" in out


def test_seq_parallel_matches_baseline():
    out = _run_forced("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L, transformer
        from repro.configs import REGISTRY, smoke_config
        cfg = smoke_config(REGISTRY["smollm-135m"])
        params = transformer.init_lm(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 16), dtype=np.int32))
        base = transformer.forward_train(cfg, params, toks)
        mesh = make_mesh((2, 4), ("data", "model"))
        L.set_mesh(mesh); L.set_seq_parallel(True)
        sp = jax.jit(lambda p, t: transformer.forward_train(cfg, p, t))(params, toks)
        L.set_mesh(None); L.set_seq_parallel(False)
        np.testing.assert_allclose(np.asarray(base), np.asarray(sp),
                                   rtol=2e-2, atol=2e-2)
        print("SP_OK")
    """)
    assert "SP_OK" in out


def test_compressed_training_still_learns():
    """int8 error-feedback compression must not break optimization."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import REGISTRY, smoke_config
    from repro.core.config import TrainConfig
    from repro.models import zoo
    from repro.train.train_loop import init_state, make_train_step
    cfg = smoke_config(REGISTRY["smollm-135m"])
    api = zoo.build(cfg)
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=0, compress_grads=True)
    state = init_state(api.init(jax.random.PRNGKey(0)), tcfg)
    assert state.error is not None
    step = jax.jit(make_train_step(api.loss, tcfg))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks),
             "labels": jnp.asarray(np.roll(toks, -1, 1))}
    first = None
    for _ in range(25):
        state, m = step(state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first - 0.5
