"""The harness finds configurations, cells, graphs and metric readers by
name, so that each is added by adding files; and ``BENCHMARK.json``
keeps to the form its readers expect."""
import json
import re

import pytest

from conftest import BENCH_DIR, REPO, make_root

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
    assert cfg["dataset"]["feat_dim"] == cfg["model"]["gcn_in_dim"]
    assert cfg["dataset"]["n_classes"] == cfg["model"]["n_classes"]
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
