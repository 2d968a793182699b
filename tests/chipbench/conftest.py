"""Fixtures of the chip benchmark's tests: a tiny copy of the benchmark's
directory (its graphs, model families, metric readers and peaks, a tiny
configuration and tiny cells) that the harness runs on the CPU."""
import contextlib
import copy
import io
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH_DIR = REPO / "chipbench"
TINY_CELLS = ("tiny-w1", "tiny-w4")


def tiny_config() -> dict:
    """The R-MAT configuration at a size the CPU runs in seconds."""
    cfg = json.loads((BENCH_DIR / "configs"
                      / "graphgen-gcn-rmat23.json").read_text())
    cfg["name"] = "tiny-rmat"
    cfg["model"].update(gcn_in_dim=16, gcn_hidden=32, n_classes=8,
                        fanouts=[4, 3], cache_rows=64)
    cfg["dataset"].update(
        params={"scale": 10, "edge_factor": 8, "a": 0.57, "b": 0.19,
                "c": 0.19},
        n_edges=16384, feat_dim=16, n_classes=8)
    return cfg


def tiny_cell(workers: int) -> dict:
    """A tiny cell holding the limits of ``gcn-rmat23-w1``."""
    wl = json.loads((BENCH_DIR / "workloads"
                     / "gcn-rmat23-w1.json").read_text())
    wl.update(config="tiny-rmat", chips=workers, workers=workers,
              seeds_per_worker=32 // workers, warmup_steps=4)
    return wl


def make_root(path: Path) -> Path:
    """A benchmark directory at ``path`` with the tiny cells."""
    for sub in ("graphs", "models", "metrics"):
        shutil.copytree(BENCH_DIR / sub, path / sub)
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"])
    (path / "peaks.json").write_text(json.dumps(peaks))
    (path / "configs").mkdir()
    (path / "workloads").mkdir()
    (path / "configs" / "tiny-rmat.json").write_text(
        json.dumps(tiny_config()))
    for name, w in zip(TINY_CELLS, (1, 4)):
        (path / "workloads" / f"{name}.json").write_text(
            json.dumps(tiny_cell(w)))
    return path


def tiny_bench() -> dict:
    """``BENCHMARK.json`` with every metric reported by the tiny cells."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = copy.deepcopy(bench)
    for m in bench["end_to_end"] + bench["per_layer"]:
        m["workloads"] = list(TINY_CELLS)
    return bench


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("chipbench"))


@contextlib.contextmanager
def kept_cache_config():
    """Restore JAX's compile-cache settings that a harness run changes."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_enable_compilation_cache")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def run_tiny(root: Path, cell: str = "tiny-w1", seed: int = 2 ** 31 + 7,
             seconds: float = 0.5, trace: int = 0, **test_only):
    """One run of a tiny cell through ``harness.main`` on the CPU:
    ``(exit code, last stdout line as JSON or None, stderr)``."""
    from chipbench import harness
    out, err = io.StringIO(), io.StringIO()
    with kept_cache_config(), contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(err):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, bench=tiny_bench(),
                          data_dir=root / ".data",
                          **{"platform": "cpu", **test_only})
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
