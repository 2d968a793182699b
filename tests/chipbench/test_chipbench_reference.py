"""The plain float32 reference against the program's training step at a
tiny size, and the control (the reference in bfloat16) failing the
limits of every cell."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH_DIR

from chipbench import checks, harness, reference

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


def _program_steps(params0, table, labels, batches):
    """The program's train function (``train_gcn``'s step 4) over the
    same batches: losses, first clipped gradient, last parameters."""
    from repro.core.config import TrainConfig
    from repro.graph.subgraph import SubgraphBatch
    from repro.train.optimizer import init_adam
    depth = len(batches[0]["hops"])
    train_fn = jax.jit(harness._train_fn(TrainConfig(**TRAIN)))
    params = harness.to_program_params(
        {k: jnp.asarray(v) for k, v in params0.items()}, depth)
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
                     harness.from_program_params(opt.m).items()}
    return losses, grad1, harness.from_program_params(params)


@pytest.mark.parametrize("fanouts", [(5,), (4, 3), (3, 2, 2)])
def test_reference_matches_program_step(fanouts):
    rng = np.random.default_rng(len(fanouts))
    n, d, h, c, b = 200, 12, 16, 5, 24
    table = (0.1 * rng.standard_normal((n, d))).astype(np.float32)
    labels = rng.integers(0, c, n).astype(np.int32)
    batches = [_tree(rng, b, fanouts, n) for _ in range(3)]
    params0 = {k: np.asarray(v) for k, v in reference.init_params(
        jax.random.PRNGKey(3), len(fanouts), d, h, c).items()}
    prog = _program_steps(params0, table, labels, batches)
    ref = reference.run_steps(TRAIN, params0, jnp.asarray(table),
                              jnp.asarray(labels), batches, block=10)
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
                               jnp.asarray(labels), batches, block=10,
                               dtype=jnp.bfloat16)
    cgaps = checks.model_gaps(*ctrl[:1], ctrl[1], params0, ctrl[2], *ref)
    for path in sorted((BENCH_DIR / "workloads").glob("*.json")):
        lim = json.loads(path.read_text())["limits"]
        assert not checks.verdict(cgaps, {k: lim[k] for k in cgaps}), (
            path.name, cgaps)


def test_leaf_gap_and_moved_leaves():
    ref = {"a": np.full(4, 1.0), "b": np.full(4, 2.0), "c": np.full(4, 1e-9)}
    prog = {"a": np.full(4, 1.1), "b": np.full(4, 2.0), "c": np.zeros(4)}
    # leaf a: |2.2 - 2| over max(2, median 2) = 0.1
    assert checks.leaf_gap(prog, ref) == pytest.approx(0.1)
    assert checks.moved_leaves(ref) == ["a", "b"]
    assert checks.verdict({"x": 1.0}, {"x": 2.0})
    assert not checks.verdict({"x": float("nan")}, {"x": 2.0})
    assert not checks.verdict({}, {"x": 2.0})
