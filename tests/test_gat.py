"""The GAT family through the program's own paths: its named scopes in
the compiled training step, the GNN-family predicate that routes it,
``train_gcn`` and ``GraphServer`` taking it from ``zoo.build``."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, REGISTRY, get_config, smoke_config
from repro.core.config import GNN_FAMILIES, ModelConfig
from repro.models import gat, zoo
from test_models_smoke import _random_subgraph_batch

SCOPES = ("gat_project", "gat_attend", "gat_out")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scopes(op_name: str) -> set:
    """The parts of a JAX name stack, transform wrappers such as
    ``jvp(...)`` and ``transpose(...)`` unwrapped."""
    out = set()
    for part in op_name.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", part)):
            part = m.group(1)
        out.add(part)
    return out


def test_gat_scopes_in_forward_and_backward():
    """The compiled loss-and-gradient of the tiny GAT names each of its
    scopes in the ops of the forward pass and of ``transpose(jvp(...))``,
    the backward pass, so a device trace splits the model by them."""
    cfg = smoke_config(get_config("graphgen-gat"))
    model = zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _random_subgraph_batch(cfg.fanouts, 6, cfg.gcn_in_dim,
                                   cfg.n_classes)
    text = jax.jit(jax.value_and_grad(model.loss)).lower(
        params, batch).compile().as_text()
    names = _OP_NAME.findall(text)
    for scope in SCOPES:
        fwd = [n for n in names
               if scope in _scopes(n) and "transpose(" not in n]
        bwd = [n for n in names
               if scope in _scopes(n) and "transpose(jvp(" in n]
        assert fwd, f"no forward op under {scope}"
        assert bwd, f"no backward op under {scope}"


def test_gnn_families_are_one_predicate():
    """``is_gnn`` is the one test for a GNN family: a GAT is kept out of
    the LM archs and shrunk by ``smoke_config`` as a GNN, multi-headed."""
    assert GNN_FAMILIES == ("gcn", "gat")
    gnn = {n for n, c in REGISTRY.items() if c.is_gnn}
    assert gnn == {"graphgen-gcn", "graphgen-gcn-deep", "graphgen-sage",
                   "graphgen-gat"}
    assert not gnn & set(ASSIGNED_ARCHS)
    small = smoke_config(get_config("graphgen-gat"))
    assert (small.family, small.gcn_in_dim, small.gcn_hidden) == ("gat", 16,
                                                                  32)
    assert small.gat_heads == 2 and len(small.fanouts) == 3
    assert smoke_config(get_config("graphgen-gcn")).gat_heads == 0


def test_gat_config_is_checked():
    cfg = get_config("graphgen-gat")
    assert (cfg.gcn_in_dim, cfg.gcn_hidden, cfg.gat_heads, cfg.n_classes,
            cfg.fanouts) == (1024, 512, 4, 2983, (15, 10, 5))
    with pytest.raises(ValueError, match="gat_heads"):
        dataclasses.replace(cfg, gat_heads=3)
    with pytest.raises(ValueError, match="gat_heads"):
        ModelConfig(name="x", family="gat", gcn_hidden=8)


def test_zoo_gives_gnn_families_logits():
    """A GNN family's ``logits`` is its forward pass; its loss is the
    mean NLL of those logits."""
    cfg = smoke_config(get_config("graphgen-gat"))
    model = zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = _random_subgraph_batch(cfg.fanouts, 6, cfg.gcn_in_dim,
                                   cfg.n_classes, seed=1)
    logits = model.logits(params, batch)
    assert logits.shape == (6, cfg.n_classes)
    np.testing.assert_array_equal(logits, gat.gat_forward(params, batch))
    logp = jax.nn.log_softmax(logits)
    want = -jnp.mean(jnp.take_along_axis(logp, batch.labels[:, None], 1))
    np.testing.assert_allclose(model.loss(params, batch), want, rtol=1e-6)
    assert zoo.build(smoke_config(get_config("graphgen-gcn"))).logits


def test_train_gcn_trains_a_gat(tmp_path):
    """``graphgen-gat`` trains through ``train_gcn``, the GCN's own entry
    point, with finite losses over a tree of the configured depth."""
    from repro.launch.train import build_parser, train_gcn
    args = build_parser().parse_args([
        "--arch", "graphgen-gat", "--smoke", "--steps", "3", "--nodes",
        "400", "--batch-per-worker", "4", "--ckpt-dir", str(tmp_path)])
    out = train_gcn(args)
    assert len(out["losses"]) == 3 and np.isfinite(out["losses"]).all()
    assert out["nodes_per_iter"] == 4 * (1 + 4 + 4 * 3 + 4 * 3 * 2)
    assert out["n_dropped"] == 0


def test_graph_server_serves_a_gat():
    """``GraphServer`` answers requests with a GAT's ``logits`` from
    ``zoo.build``, with no compile on the request path."""
    from repro.core.generation import make_distributed_generator
    from repro.core.partition import partition_edges
    from repro.graph.synthetic import node_features, node_labels, powerlaw_graph
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import GraphServer
    n = 200
    cfg = dataclasses.replace(smoke_config(get_config("graphgen-gat")),
                              fanouts=(4, 3))
    g = powerlaw_graph(n, avg_degree=6, n_hot=3, hot_degree=50, seed=0)
    out = make_distributed_generator(
        make_mesh((1,), ("data",)), partition_edges(g, 1),
        node_features(n, cfg.gcn_in_dim), node_labels(n, cfg.n_classes),
        fanouts=cfg.fanouts)
    model = zoo.build(cfg)
    server = GraphServer(out[0], out[1], model.init(jax.random.PRNGKey(2)),
                         None, logits=model.logits, buckets=(4, 8),
                         n_workers=1)
    assert server.warmup() == 2
    preds = server.serve(np.arange(5))
    assert preds.shape == (5,) and preds.dtype == np.int32
    assert ((0 <= preds) & (preds < cfg.n_classes)).all()
    assert server.compile_count() == 2
