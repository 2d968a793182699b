"""The plain float32 reference (the shared ``reference.py`` over the GCN
family's ``models/gcn.py``) against the program's training step at a
tiny size, and the control (the reference in bfloat16) failing the
limits of every cell."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH_DIR

from chipbench import checks, harness, reference

GCN = harness.family_module("gcn")
TRAIN = {"learning_rate": 1e-3, "weight_decay": 0.1, "beta1": 0.9,
         "beta2": 0.95, "eps": 1e-8, "grad_clip": 1.0, "warmup_steps": 100,
         "total_steps": 1000}


def _tree(rng, b, fanouts, n_nodes):
    """Seeds, ids and chained masks of a random padded tree."""
    seeds = rng.choice(n_nodes, b, replace=False).astype(np.int32)
    shape, hops, masks, parent = (b,), [], [], np.ones((b,), bool)
    for k in fanouts:
        shape = shape + (k,)
        hops.append(rng.integers(0, n_nodes, shape).astype(np.int32))
        m = (rng.random(shape) < 0.8) & parent[..., None]
        masks.append(m)
        parent = m
    return {"seeds": seeds, "hops": hops, "masks": masks}


def _model(d, h, c, fanouts) -> dict:
    return {"family": "gcn", "gcn_in_dim": d, "gcn_hidden": h,
            "n_classes": c, "fanouts": list(fanouts)}


def _program_steps(model, params0, table, labels, batches):
    """The program's train function (``train_gcn``'s step 4) over the
    same batches: losses, first clipped gradient, last parameters."""
    from repro.core.config import TrainConfig
    from repro.graph.subgraph import SubgraphBatch
    from repro.train.optimizer import init_adam
    depth = len(batches[0]["hops"])
    train_fn = jax.jit(harness._train_fn(
        TrainConfig(**TRAIN),
        harness.model_config({"name": "tiny", "model": model})))
    params = GCN.to_program(
        {k: jnp.asarray(v) for k, v in params0.items()}, model, depth)
    opt = init_adam(params)
    losses, grad1 = [], None
    for b in batches:
        x_hops = [table[h] * m[..., None] for h, m in zip(b["hops"],
                                                          b["masks"])]
        batch = SubgraphBatch(
            seeds=jnp.asarray(b["seeds"]), hops=tuple(map(jnp.asarray,
                                                          b["hops"])),
            masks=tuple(map(jnp.asarray, b["masks"])),
            x_seed=jnp.asarray(table[b["seeds"]]),
            x_hops=tuple(map(jnp.asarray, x_hops)),
            labels=jnp.asarray(labels[b["seeds"]]),
            n_dropped=jnp.zeros((1,), jnp.int32))
        params, opt, loss = train_fn(params, opt, batch)
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {k: v / (1 - TRAIN["beta1"]) for k, v in
                     GCN.from_program(opt.m).items()}
    return losses, grad1, GCN.from_program(params)


@pytest.mark.parametrize("fanouts", [(5,), (4, 3), (3, 2, 2)])
def test_reference_matches_program_step(fanouts):
    rng = np.random.default_rng(len(fanouts))
    n, d, h, c, b = 200, 12, 16, 5, 24
    table = (0.1 * rng.standard_normal((n, d))).astype(np.float32)
    labels = rng.integers(0, c, n).astype(np.int32)
    batches = [_tree(rng, b, fanouts, n) for _ in range(3)]
    model = _model(d, h, c, fanouts)
    params0 = {k: np.asarray(v) for k, v in GCN.init(
        jax.random.PRNGKey(3), model, len(fanouts)).items()}
    prog = _program_steps(model, params0, table, labels, batches)
    ref = reference.run_steps(TRAIN, params0, jnp.asarray(table),
                              jnp.asarray(labels), batches,
                              forward=GCN.forward, block=10)
    np.testing.assert_allclose(prog[0], ref[0], rtol=2e-6)
    for k in params0:
        np.testing.assert_allclose(prog[1][k], ref[1][k], rtol=2e-4,
                                   atol=1e-7)
        np.testing.assert_allclose(prog[2][k], ref[2][k], rtol=1e-5,
                                   atol=1e-7)
    gaps = checks.model_gaps(*prog[:1], prog[1], params0, prog[2],
                             *ref)
    limits = json.loads((BENCH_DIR / "workloads"
                         / "gcn-rmat23-w1.json").read_text())["limits"]
    assert checks.verdict(gaps, {k: limits[k] for k in gaps})

    # the control: the reference in bfloat16 put in the program's place
    ctrl = reference.run_steps(TRAIN, params0, jnp.asarray(table),
                               jnp.asarray(labels), batches,
                               forward=GCN.forward, block=10,
                               dtype=jnp.bfloat16)
    cgaps = checks.model_gaps(*ctrl[:1], ctrl[1], params0, ctrl[2], *ref)
    for path in sorted((BENCH_DIR / "workloads").glob("*.json")):
        lim = json.loads(path.read_text())["limits"]
        assert not checks.verdict(cgaps, {k: lim[k] for k in cgaps}), (
            path.name, cgaps)


@pytest.mark.parametrize("depth,dims,digest,w_self00,w_out_last", [
    (2, (12, 16, 5), "4f2f6e22cf6efb12", -0.4548597037792206,
     0.236572265625),
    (3, (12, 16, 5), "48438f8791836b60", -0.4548597037792206,
     -0.09261874109506607),
    (2, (128, 256, 64), "03402e146372e019", -0.12282615900039673,
     -0.09564101696014404),
])
def test_gcn_init_draws_the_former_weights(depth, dims, digest, w_self00,
                                           w_out_last):
    """``models/gcn.py``'s ``init``, jitted as a run calls it, draws the
    arrays that ``reference.init_params`` drew before the GCN became a
    family module (values and digest pinned from it, key 3), so equal
    seeds give equal weights bit for bit."""
    model = _model(*dims, [2] * depth)
    flat = jax.jit(lambda key: GCN.init(key, model, depth))(
        jax.random.PRNGKey(3))
    h = hashlib.sha256()
    for k in sorted(flat):
        h.update(k.encode())
        h.update(np.asarray(flat[k]).tobytes())
    assert h.hexdigest()[:16] == digest
    assert float(flat["layers.0.w_self"][0, 0]) == w_self00
    assert float(flat["w_out"][-1, -1]) == w_out_last
    assert all(v.dtype == jnp.float32 for v in flat.values())


def test_leaf_gap_and_moved_leaves():
    ref = {"a": np.full(4, 1.0), "b": np.full(4, 2.0), "c": np.full(4, 1e-9)}
    prog = {"a": np.full(4, 1.1), "b": np.full(4, 2.0), "c": np.zeros(4)}
    # leaf a: |2.2 - 2| over max(2, median 2) = 0.1
    assert checks.leaf_gap(prog, ref) == pytest.approx(0.1)
    assert checks.moved_leaves(ref) == ["a", "b"]
    assert checks.verdict({"x": 1.0}, {"x": 2.0})
    assert not checks.verdict({"x": float("nan")}, {"x": 2.0})
    assert not checks.verdict({}, {"x": 2.0})


def test_calibration_readings_through_the_family(tiny_root):
    """``calibrate.readings`` on a tiny cell's recorded steps: the
    program within the cell's limits, the bfloat16 control and the half
    batch, both run through the family's reference, outside them."""
    from chipbench import calibrate
    wl, cfg = harness.load_cell("tiny-w1", tiny_root)
    s = harness.build(wl, cfg, tiny_root, tiny_root / ".data")
    run = harness.Run(s, 2 ** 31 + 3)
    run.record()
    run.carry = None
    out = calibrate.readings(run)
    lim = wl["limits"]
    assert checks.verdict(out["program"], {k: lim[k] for k in out["program"]})
    for variant in ("control", "half_batch"):
        assert not checks.verdict(out[variant],
                                  {k: lim[k] for k in out[variant]}), out
    assert "one_worker" not in out
