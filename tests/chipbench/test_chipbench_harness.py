"""The harness finds configurations, cells, graphs, model families and
metric readers by name, so that each is added by adding files; and
``BENCHMARK.json`` keeps to the form its readers expect."""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH_DIR, REPO, make_root, run_tiny

from chipbench import datasets, harness

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    wl, cfg = harness.load_cell(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert wl["config"] == entry["config"] == cfg["name"]
    assert wl["chips"] == entry["chips"] == wl["workers"]
    assert wl["why"] == entry["why"] and len(wl["why"]) <= 200
    assert wl["warmup_steps"] > harness.Run.RECORDED
    assert cfg["dataset"]["n_classes"] == cfg["model"]["n_classes"]
    # the family's reference takes the dataset's rows and gives its classes
    fam = harness.family_module(cfg["model"]["family"])
    assert fam.BLOCK > 0
    sds = jax.ShapeDtypeStruct
    shape, x_hops, masks = (2,), [], []
    for k in cfg["model"]["fanouts"]:
        shape += (k,)
        x_hops.append(sds(shape + (cfg["dataset"]["feat_dim"],), jnp.float32))
        masks.append(sds(shape, jnp.bool_))
    logits = jax.eval_shape(
        lambda key, x_seed, x_hops, masks: fam.forward(
            fam.init(key, cfg["model"], len(x_hops)), x_seed, x_hops, masks),
        jax.random.PRNGKey(0), sds((2, cfg["dataset"]["feat_dim"]),
                                   jnp.float32), x_hops, masks)
    assert logits.shape == (2, cfg["dataset"]["n_classes"])
    datasets.graph_builder(cfg["dataset"]["graph"])
    harness.model_config(cfg)
    harness.train_config(cfg)
    for m in harness.cell_metrics(BENCH, cell, "per_layer"):
        assert callable(harness.metric_reader(m["name"]))
    assert {m["name"] for m in harness.cell_metrics(
        BENCH, cell, "end_to_end")} == {"seeds_per_s", "peak_hbm_gib",
                                        "setup_s"}


def test_config_files_are_named_in_benchmark():
    for c in BENCH["configs"]:
        path = REPO / c["file"]
        assert path.is_file() and path.stem == c["name"]
        assert json.loads(path.read_text())["reduced"] == c["reduced"]


def test_benchmark_names_units_and_links():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(NAME.match(w["traffic"]) for w in BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    # a metric that only some cells report lists them
    for m in BENCH["per_layer"]:
        assert m["workloads"], m["name"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_device_peaks_by_kind():
    assert harness.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.device_peaks("TPU v9 imaginary")


def test_new_cell_metric_and_graph_are_found_from_files(tmp_path):
    """A later change adds a cell, a configuration, a graph generator
    and a metric reader as files, and the harness finds each by name."""
    root = make_root(tmp_path)
    (root / "graphs" / "ring.py").write_text(
        "import numpy as np\n"
        "def build(params, seed):\n"
        "    n = int(params['n'])\n"
        "    return np.arange(n + 1, dtype=np.int32), "
        "((np.arange(n) + 1) % n).astype(np.int32)\n")
    (root / "metrics" / "steps.seen.py").write_text(
        "def read(ctx):\n    return ctx.window_steps\n")
    cfg = json.loads((root / "configs" / "tiny-rmat.json").read_text())
    cfg.update(name="tiny-ring",
               dataset=dict(cfg["dataset"], graph="ring", params={"n": 64},
                            n_edges=64))
    (root / "configs" / "tiny-ring.json").write_text(json.dumps(cfg))
    wl = json.loads((root / "workloads" / "tiny-w1.json").read_text())
    (root / "workloads" / "ring-w1.json").write_text(
        json.dumps(dict(wl, config="tiny-ring")))
    wl2, cfg2 = harness.load_cell("ring-w1", root)
    assert cfg2["dataset"]["graph"] == "ring"
    indptr, indices = datasets.load_graph(cfg2["dataset"], root,
                                          tmp_path / ".data")
    assert len(indices) == 64
    read = harness.metric_reader("steps.seen", root)
    assert read(type("C", (), {"window_steps": 7})) == 7
    with pytest.raises(FileNotFoundError):
        harness.load_cell("no-such-cell", root)
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such_metric", root)


#: a model family added as a file: the GCN without its self weights
TOY_FAMILY = '''
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import costs

BLOCK = 8
SCALE = 1.0


def init(key, model, depth):
    hidden, shapes, din = model["gcn_hidden"], {}, model["gcn_in_dim"]
    for i in range(depth):
        shapes[f"layers.{i}.w_nbr"] = (din, hidden)
        shapes[f"layers.{i}.b"] = (hidden,)
        din = hidden
    shapes["w_out"] = (hidden, model["n_classes"])
    shapes["b_out"] = (model["n_classes"],)
    out = {}
    for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                shapes.items()):
        lim = math.sqrt(6.0 / sum(shape)) if len(shape) == 2 else 0.0
        out[name] = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
    return out


def to_program(flat, model, depth):
    return dict(flat)


def from_program(params):
    return {k: np.asarray(v) for k, v in params.items()}


def forward(params, x_seed, x_hops, masks, dtype=jnp.float32):
    p = {k: v.astype(dtype) for k, v in params.items()}
    reps = [x_seed.astype(dtype)] + [x.astype(dtype) for x in x_hops]
    for i in range(len(x_hops)):
        new = []
        for v in range(len(x_hops) - i):
            m = masks[v].astype(dtype)
            agg = (jnp.sum(reps[v + 1] * m[..., None], axis=-2)
                   / jnp.maximum(jnp.sum(m, axis=-1, keepdims=True), 1))
            new.append(jax.nn.relu(agg @ p[f"layers.{i}.w_nbr"]
                                   + p[f"layers.{i}.b"]))
        reps = new
    return SCALE * (reps[0] @ p["w_out"] + p["b_out"])


def flops_per_seed(fanouts, model):
    levels, depth = costs.tree_levels(fanouts), len(fanouts)
    f, din = 0, model["gcn_in_dim"]
    for i in range(depth):
        f += sum(levels[:depth - i]) * 2 * din * model["gcn_hidden"]
        din = model["gcn_hidden"]
    f += 2 * model["gcn_hidden"] * model["n_classes"]
    return {"forward": f, "backward": 2 * f}
'''


def _toy_program_loss(params, batch):
    """The program's side of the toy family, as a program model would
    give it through ``zoo.build``: mean NLL of the same equations."""
    reps = [batch.x_seed] + list(batch.x_hops)
    depth = len(batch.x_hops)
    for i in range(depth):
        reps = [jax.nn.relu(
            (jnp.sum(reps[v + 1] * batch.masks[v][..., None], axis=-2)
             / jnp.maximum(batch.masks[v].sum(-1, keepdims=True), 1))
            @ params[f"layers.{i}.w_nbr"] + params[f"layers.{i}.b"])
            for v in range(depth - i)]
    logits = reps[0] @ params["w_out"] + params["b_out"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch.labels[:, None], 1))


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A tiny benchmark directory whose ``tiny-w1`` cell runs a family
    added as ``models/toy.py``, with the same family registered in the
    program's ``zoo.build`` for this test only."""
    from repro.models import zoo
    root = make_root(tmp_path)
    (root / "models" / "toy.py").write_text(TOY_FAMILY)
    cfg = json.loads((root / "configs" / "tiny-rmat.json").read_text())
    # the program's ModelConfig holds the family's sizes: the toy keeps
    # the GCN's keys, with its own hidden width
    model = dict(cfg["model"], family="toy", gcn_hidden=24)
    (root / "configs" / "tiny-toy.json").write_text(
        json.dumps(dict(cfg, name="tiny-toy", model=model)))
    wl = json.loads((root / "workloads" / "tiny-w1.json").read_text())
    (root / "workloads" / "tiny-w1.json").write_text(
        json.dumps(dict(wl, config="tiny-toy")))
    real = zoo.build

    def build(mcfg):
        if mcfg.family == "toy":
            return zoo.ModelAPI(cfg=mcfg, init=None, loss=_toy_program_loss,
                                decode=None, init_cache=None)
        return real(mcfg)
    monkeypatch.setattr(zoo, "build", build)
    return root


def test_new_model_family_is_found_from_files(toy_root):
    """A family added as a file runs a cell end to end with ``correct``
    true, and a fault planted in its reference (logits scaled by 1.25)
    makes ``correct`` false.  At this size the logits are small: a scale
    of 1.01 moves ``loss_gap`` from 9e-8 to only 1.3e-5, under the
    cells' limit of 5e-5."""
    rc, result, err = run_tiny(toy_root, seed=2 ** 31 + 9, seconds=0.3)
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    path = toy_root / "models" / "toy.py"
    path.write_text(path.read_text().replace("SCALE = 1.0", "SCALE = 1.25"))
    rc, broken, err = run_tiny(toy_root, seed=2 ** 31 + 9, seconds=0.3)
    assert rc == 0, err
    assert broken["correct"] is False
    assert (broken["checks"]["loss_gap"]["value"]
            > broken["checks"]["loss_gap"]["limit"]), broken["checks"]


def test_unknown_model_family_is_refused(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(FileNotFoundError, match="no model family"):
        harness.family_module("no_such_family", root)
    cfg = json.loads((root / "configs" / "tiny-rmat.json").read_text())
    cfg["model"]["family"] = "no_such_family"
    (root / "configs" / "tiny-rmat.json").write_text(json.dumps(cfg))
    wl, cfg = harness.load_cell("tiny-w1", root)
    with pytest.raises(FileNotFoundError, match="no_such_family"):
        harness.build(wl, cfg, root, tmp_path / ".data")
    assert harness.family_module("gcn", root).BLOCK == 512


def test_sub_seeds_take_large_seeds():
    a = harness.sub_seeds(2 ** 31 + 5, 2)
    assert a == harness.sub_seeds(2 ** 31 + 5, 2)
    assert a != harness.sub_seeds(2 ** 31 + 6, 2)
    assert all(0 <= s < 2 ** 31 for s in a)


def test_benchmark_dir_holds_no_data():
    """Built graphs and traces are made at run time and ignored by git."""
    ignore = (REPO / ".gitignore").read_text()
    assert "chipbench/.data/" in ignore and "chipbench/.traces/" in ignore
    assert harness.TRACE_DIR.parent == datasets.DATA_DIR.parent == BENCH_DIR
