"""The GAT family: the program's ``repro.models.gat`` against the plain
float32 reference of ``chipbench/models/gat.py`` at a tiny size, its
operation count against hand counts, and a tiny GAT cell run end to end
through the harness on the CPU."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_root, run_tiny

from chipbench import checks, harness, reference

GAT = harness.family_module("gat")


def _model(d, hidden, heads, c, fanouts) -> dict:
    return {"family": "gat", "gcn_in_dim": d, "gcn_hidden": hidden,
            "gat_heads": heads, "n_classes": c, "fanouts": list(fanouts)}


def _batch(rng, b, d, c, fanouts, childless: float):
    """A padded tree of random rows with chained masks; ``childless`` is
    the share of parents at each level whose every child is masked out."""
    from repro.graph.subgraph import SubgraphBatch
    shape, parent = (b,), np.ones((b,), bool)
    hops, masks, x_hops = [], [], []
    for k in fanouts:
        none = rng.random(shape) < childless
        shape = shape + (k,)
        m = (rng.random(shape) < 0.7) & parent[..., None] & ~none[..., None]
        masks.append(m)
        hops.append(np.zeros(shape, np.int32))
        x = rng.standard_normal(shape + (d,)).astype(np.float32)
        x_hops.append(x * m[..., None])
        parent = m
    return SubgraphBatch(
        seeds=jnp.arange(b, dtype=jnp.int32),
        hops=tuple(map(jnp.asarray, hops)),
        masks=tuple(map(jnp.asarray, masks)),
        x_seed=jnp.asarray(rng.standard_normal((b, d)).astype(np.float32)),
        x_hops=tuple(map(jnp.asarray, x_hops)),
        labels=jnp.asarray(rng.integers(0, c, b).astype(np.int32)),
        n_dropped=jnp.zeros((1,), jnp.int32))


# (heads, fanouts, share of parents with every child masked out)
CASES = {
    "two_heads": (2, (4, 3), 0.0),
    "childless_parents": (2, (4, 3), 0.5),
    "one_head": (1, (4, 3), 0.2),
    "depth_one": (2, (4,), 0.2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_matches_reference(case):
    """Logits, loss and every gradient leaf of the program's GAT (as
    ``zoo.build`` gives it) equal the reference's at ``highest``
    precision.  Both are float32 and differ only in the order of their
    sums (the reference's softmax runs over the parent and its children
    concatenated, the program's keeps the parent's term apart): that is
    a few float32 ulps, so 1e-5 relative, with an absolute floor of 1e-6
    for the leaves whose entries are near 0."""
    from repro.models import zoo
    heads, fanouts, childless = CASES[case]
    d, hidden, c, b = 16, 8 * heads, 5, 12
    model = _model(d, hidden, heads, c, fanouts)
    rng = np.random.default_rng(len(case))
    batch = _batch(rng, b, d, c, fanouts, childless)
    if childless:
        assert not np.asarray(batch.masks[0]).any(-1).all()
    flat = GAT.init(jax.random.PRNGKey(7), model, len(fanouts))
    # non-zero biases, so that their path is compared too
    flat = {k: v + 0.05 if k.endswith("b") or k == "b_out" else v
            for k, v in flat.items()}
    api = zoo.build(harness.model_config({"name": "tiny", "model": model}))
    params = GAT.to_program(flat, model, len(fanouts))

    def ref_loss(p):
        return reference.nll_sum(GAT.forward, p, batch.x_seed, batch.x_hops,
                                 batch.masks, batch.labels) / b

    with jax.default_matmul_precision("highest"):
        logits = api.logits(params, batch)
        loss, grads = jax.value_and_grad(api.loss)(params, batch)
        ref_logits = GAT.forward(flat, batch.x_seed, batch.x_hops,
                                 batch.masks)
        r_loss, r_grads = jax.value_and_grad(ref_loss)(flat)
    np.testing.assert_allclose(logits, ref_logits, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-5)
    got = GAT.from_program(grads)
    assert set(got) == set(r_grads)
    for k in got:
        assert np.isfinite(got[k]).all(), k
        np.testing.assert_allclose(got[k], r_grads[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)


def test_parent_without_children_attends_to_itself():
    """With every child masked out, one layer's output is the parent's
    own projection plus the bias: the softmax over the set ``{v}`` is 1."""
    model = _model(6, 4, 2, 3, (3,))
    rng = np.random.default_rng(0)
    batch = _batch(rng, 4, 6, 3, (3,), childless=1.0)
    flat = GAT.init(jax.random.PRNGKey(1), model, 1)
    # an identity head reads the seed level's representation
    flat = dict(flat, w_out=jnp.eye(4, 3), b_out=jnp.zeros((3,)))
    want = batch.x_seed @ flat["layers.0.w"] @ jnp.eye(4, 3)
    got = GAT.forward(flat, batch.x_seed, batch.x_hops, batch.masks)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fanouts,dims,fwd,bwd", [
    # the cell: 916 rows x 2*1024*512, 166 and 16 rows x 2*512*512,
    # head 2*512*2983; backward: every weight, inputs after layer 1
    ((15, 10, 5), (1024, 512, 2983),
     960_495_616 + 87_031_808 + 8_388_608 + 3_054_592,
     960_495_616 + 2 * (87_031_808 + 8_388_608 + 3_054_592)),
    # one hop: levels 0 and 1 projected, 5 rows x 2*16*8, head 2*8*5
    ((4,), (16, 8, 5), 1_280 + 80, 1_280 + 160),
])
def test_gat_flops_per_seed(fanouts, dims, fwd, bwd):
    model = dict(zip(("gcn_in_dim", "gcn_hidden", "n_classes"), dims))
    assert GAT.flops_per_seed(fanouts, model) == {"forward": fwd,
                                                  "backward": bwd}


def test_param_count_matches_the_family():
    """``ModelConfig.param_count`` counts what ``init`` draws."""
    from repro.configs import get_config
    cfg = get_config("graphgen-gat")
    model = dataclasses.asdict(cfg)
    flat = jax.eval_shape(lambda k: GAT.init(k, model, len(cfg.fanouts)),
                          jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(int(np.prod(v.shape))
                                    for v in flat.values())


def test_gat_graph_is_built_under_its_own_key():
    """The scale-20 graph of the GAT configuration is kept apart from the
    scale-23 graph, which both GCN configurations still read as one."""
    from conftest import BENCH_DIR
    from chipbench import datasets
    keys = {p.stem: datasets.graph_key(json.loads(p.read_text())["dataset"])
            for p in (BENCH_DIR / "configs").glob("*.json")}
    assert keys["graphgen-gcn-rmat23"] == keys["graphgen-gcn-deep-rmat23"]
    assert keys["graphgen-gat-rmat20"] != keys["graphgen-gcn-rmat23"]


def _tiny_gat_root(path):
    """A tiny benchmark directory whose ``tiny-w1`` cell runs the GAT
    configuration at CPU size, with the GAT cell's limits."""
    root = make_root(path)
    real = json.loads((harness.HERE / "configs"
                       / "graphgen-gat-rmat20.json").read_text())
    tiny = json.loads((root / "configs" / "tiny-rmat.json").read_text())
    model = dict(real["model"], gcn_in_dim=16, gcn_hidden=16, gat_heads=2,
                 n_classes=8, fanouts=[4, 3], cache_rows=64,
                 cache_l1_rows=16)
    (root / "configs" / "tiny-gat.json").write_text(json.dumps(
        dict(real, name="tiny-gat", model=model, dataset=tiny["dataset"])))
    wl = json.loads((harness.HERE / "workloads"
                     / "gat-rmat20-w1.json").read_text())
    small = json.loads((root / "workloads" / "tiny-w1.json").read_text())
    (root / "workloads" / "tiny-w1.json").write_text(json.dumps(
        dict(small, config="tiny-gat", limits=wl["limits"])))
    return root


@pytest.fixture(scope="module")
def gat_root(tmp_path_factory):
    return _tiny_gat_root(tmp_path_factory.mktemp("chipbench_gat"))


def test_tiny_gat_cell_runs_end_to_end(gat_root, tmp_path, monkeypatch):
    """The GAT configuration runs a cell through ``harness.main`` with
    ``correct`` true, its model from ``zoo.build`` and its reference from
    ``models/gat.py``, and its traced run reads ``model_roofline``.  The
    profile goes to a directory of this test's own, since other test
    files trace into the harness's fixed one at the same time."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / ".traces")
    rc, result, err = run_tiny(gat_root, seed=2 ** 31 + 11, seconds=0.3)
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    rc, traced, err = run_tiny(gat_root, seed=2 ** 31 + 11, seconds=0.3,
                               trace=1)
    assert rc == 0, err
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"]["model_roofline"]["value"] > 0


def test_gat_calibration_readings(gat_root):
    """``calibrate.readings`` on the tiny GAT cell: the program within
    the GAT cell's limits, the bfloat16 control and the half batch
    outside them."""
    from chipbench import calibrate
    wl, cfg = harness.load_cell("tiny-w1", gat_root)
    s = harness.build(wl, cfg, gat_root, gat_root / ".data")
    run = harness.Run(s, 2 ** 31 + 13)
    run.record()
    run.carry = None
    out = calibrate.readings(run)
    lim = wl["limits"]
    assert checks.verdict(out["program"], {k: lim[k] for k in out["program"]})
    for variant in ("control", "half_batch"):
        assert not checks.verdict(out[variant],
                                  {k: lim[k] for k in out[variant]}), out
