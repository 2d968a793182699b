"""Paper §2(3): tree reduction vs flat (all-to-root) aggregation for
hot-node candidate merging.

Wall time is measured on 8 forced-host devices in a subprocess (the main
process keeps 1 device).  The derived column also reports the analytic
per-worker traffic: flat root ingests (W-1)*K candidate rows, the butterfly
moves log2(W)*K per worker — the reason hot nodes stop being a bottleneck.
"""
from __future__ import annotations

import math
import os
import subprocess
import sys
import textwrap

_CODE = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import time
import jax, jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.core.generation import Candidates, merge_topk
from repro.core.tree_reduce import tree_allreduce
from repro.launch.mesh import make_mesh

W, F, K = 8, 4096, 40
mesh = make_mesh((W,), ('data',))
rng = np.random.default_rng(0)
ids = jnp.asarray(rng.integers(0, 1_000_000, (W, F, K), dtype=np.int32))
keys = jnp.asarray(rng.uniform(0, 1, (W, F, K)).astype(np.float32))

def tree(i, k):
    return tree_allreduce(Candidates(i[0], k[0]), merge_topk, 'data').ids

def flat(i, k):
    # all-gather everything to every worker, then a single wide merge
    gi = jax.lax.all_gather(i[0], 'data')            # [W, F, K]
    gk = jax.lax.all_gather(k[0], 'data')
    cand = Candidates(jnp.moveaxis(gi, 0, -1).reshape(F, K * W),
                      jnp.moveaxis(gk, 0, -1).reshape(F, K * W))
    neg, idx = jax.lax.top_k(-cand.keys, K)
    return jnp.take_along_axis(cand.ids, idx, axis=-1)

run_tree = jax.jit(shard_map(tree, mesh=mesh, in_specs=(P('data'), P('data')),
                             out_specs=P('data'), check_vma=False))
run_flat = jax.jit(shard_map(flat, mesh=mesh, in_specs=(P('data'), P('data')),
                             out_specs=P('data'), check_vma=False))
for f in (run_tree, run_flat):
    jax.block_until_ready(f(ids, keys))
out = {}
for name, f in (('tree', run_tree), ('flat', run_flat)):
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); jax.block_until_ready(f(ids, keys))
        ts.append(time.perf_counter() - t0)
    out[name] = sorted(ts)[2] * 1e6
# equivalence of results (same candidate multiset -> same min-K keys)
a = np.sort(np.asarray(run_tree(ids, keys)), axis=-1)
b = np.sort(np.asarray(run_flat(ids, keys)), axis=-1)
assert (a == b).all(), 'tree and flat merges disagree'
print(f"{out['tree']:.1f} {out['flat']:.1f}")
"""


def bench() -> list[tuple]:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                          capture_output=True, text=True, timeout=600, env=env)
    if proc.returncode != 0:
        return [("tree_reduce", 0.0, f"ERROR:{proc.stderr[-200:]}")]
    t_tree, t_flat = map(float, proc.stdout.split())
    w, k = 8, 40
    return [
        ("tree_reduce_butterfly", t_tree,
         f"per_worker_rows={int(math.log2(w))*k}"),
        ("tree_reduce_flat_gather", t_flat,
         f"per_worker_rows={(w-1)*k};speedup={t_flat/t_tree:.2f}x"),
    ]
