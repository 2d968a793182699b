"""Datasets of the benchmark: a graph from ``graphs/<name>.py``, node
features and labels, all functions of the configuration's own dataset
seed (never of ``--seed``).

The first run of a graph in a checkout builds it and keeps the CSR
under ``chipbench/.data/<graph>-<digest>/``, where the digest is of the
generator's name, parameters and seed; later runs, of any configuration
on the same graph, read it back.  Features and labels are drawn anew in every run, on the device,
in one jitted call, and copied to the host once.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
#: where built graphs are kept between runs (listed in .gitignore)
DATA_DIR = HERE / ".data"


@dataclasses.dataclass
class Dataset:
    """A graph in CSR form (sorted neighbour lists) with node features
    ``[n_nodes, feat_dim]`` float32 and labels ``[n_nodes]`` int32."""
    indptr: np.ndarray
    indices: np.ndarray
    features: np.ndarray
    labels: np.ndarray

    @property
    def n_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def n_edges(self) -> int:
        return len(self.indices)

    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def csr_from_edges(src: np.ndarray, dst: np.ndarray, n: int):
    """``(indptr, indices)`` with sorted neighbour lists from an edge
    list ``src -> dst`` over ``n`` nodes."""
    shift = max(int(n - 1).bit_length(), 1)
    key = (src.astype(np.int64) << shift) | dst.astype(np.int64)
    del src, dst
    key.sort()
    indices = (key & ((1 << shift) - 1)).astype(np.int32)
    counts = np.bincount(key >> shift, minlength=n)
    del key
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    if indptr[-1] < np.iinfo(np.int32).max:
        indptr = indptr.astype(np.int32)
    return indptr, indices


def graph_builder(name: str, root: Path = HERE):
    """The ``build(params, seed)`` function of ``graphs/<name>.py``."""
    path = Path(root) / "graphs" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no graph generator {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_graph_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build


def graph_key(spec: dict) -> str:
    """``<graph>-<digest>``: names the built graph by what defines it."""
    what = json.dumps([spec["graph"], spec["params"], int(spec["seed"])],
                      sort_keys=True)
    return f"{spec['graph']}-{hashlib.sha256(what.encode()).hexdigest()[:12]}"


def load_graph(spec: dict, root: Path = HERE, data_dir: Path = DATA_DIR):
    """The CSR of a dataset (``spec`` is a configuration's ``dataset``
    block): read from ``data_dir`` when an earlier run built it, else
    built and written there.  A stated ``n_edges`` is checked, so a
    generator that drifts is an error."""
    where = Path(data_dir) / graph_key(spec)
    files = [where / "indptr.npy", where / "indices.npy"]
    if all(f.is_file() for f in files):
        indptr, indices = (np.load(f) for f in files)
    else:
        build = graph_builder(spec["graph"], root)
        indptr, indices = build(spec["params"], int(spec["seed"]))
        where.mkdir(parents=True, exist_ok=True)
        for f, arr in zip(files, (indptr, indices)):
            tmp = f.with_suffix(".tmp.npy")
            np.save(tmp, arr)
            os.replace(tmp, f)
    want = spec.get("n_edges")
    if want is not None and len(indices) != int(want):
        raise ValueError(f"{where.name}: the graph has {len(indices)} "
                         f"edges, its configuration states {want}")
    return indptr, indices


def device_node_data(n_nodes: int, feat_dim: int, n_classes: int,
                     seed: int):
    """Features ``0.1 * N(0, 1)`` float32 and uniform labels, drawn on the
    default device in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        kx, ky = jax.random.split(key)
        x = 0.1 * jax.random.normal(kx, (n_nodes, feat_dim), jnp.float32)
        y = jax.random.randint(ky, (n_nodes,), 0, n_classes, jnp.int32)
        return x, y

    return jax.jit(draw)(jax.random.PRNGKey(seed))


def node_data(n_nodes: int, feat_dim: int, n_classes: int, seed: int):
    """``device_node_data`` copied to the host."""
    x, y = device_node_data(n_nodes, feat_dim, n_classes, seed)
    out = np.asarray(x), np.asarray(y)
    del x, y
    return out


def device_tables(spec: dict, n_nodes: int):
    """A dataset's features and labels drawn again on the device, equal
    to the host copies that ``load`` gives: the checks' own tables, made
    without a copy from the host."""
    return device_node_data(n_nodes, int(spec["feat_dim"]),
                            int(spec["n_classes"]), int(spec["seed"]))


def load(spec: dict, root: Path = HERE, data_dir: Path = DATA_DIR) -> Dataset:
    """The whole dataset of a configuration (``spec`` is its
    ``dataset`` block)."""
    indptr, indices = load_graph(spec, root, data_dir)
    x, y = node_data(len(indptr) - 1, int(spec["feat_dim"]),
                     int(spec["n_classes"]), int(spec["seed"]))
    return Dataset(indptr, indices, x, y)
