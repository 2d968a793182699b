"""Smoke run of the GCN trainer on a TPU: the repo's quickest proof that
its main path still starts, runs and serves the right rows on the chip.

    python chip_smoke.py              # one chip: kernels + W=1 training
    python chip_smoke.py --chips 4    # four chips: W=4 training only

The default phase needs one chip.  It runs each Pallas kernel that the
GCN path can reach, compiled for the chip (never interpreted), against
its ``repro.kernels.ref`` oracle at the ``graphgen-gcn`` widths.  Then it
trains ``graphgen-gcn`` (128 -> 256 -> 64, fanouts (40, 20), 4096-row
4-way cache) for a few steps through ``repro.launch.train``'s own
parser and ``train_gcn``, at W=1 with 1024 seeds, on a synthetic graph of
ogbn-products' size (2,449,029 nodes, average degree 25), and checks the
last trained batch against a plain NumPy feature table.

``--chips 4`` trains at W=4 (256 seeds per worker, sharded cache,
compact probe wire) on the same graph, checks that every worker's CSR
and table shard sit on their own chip, and regenerates the last batch
through the uncached generator, which must give bit-identical features
and masks.

The script fails, and prints no result, where JAX finds no TPU.  Its
last line on success is one JSON object naming the device.  Compiled
programs go to the persistent compilation cache
(``repro.launch.compile_cache``), so a second run compiles far less.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "graphgen-gcn"
NODES = 2_449_029           # ogbn-products
AVG_DEGREE = 25             # ogbn-products: 61,859,140 edges / 2,449,029
SEED = 0
SLACK = 2.0                 # pinned: skips the slack calibration ladder
HIT_CAP = 0                 # pinned (0 = half the probe round): no ladder


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Sums the seconds JAX spends in backend compiles (a persistent
    cache hit counts only its load) and counts persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _resident_keys(rng, c: int, assoc: int, pool):
    """A cache state as ``cache_insert`` leaves it: ids of ``pool`` at
    their true hash sets, spread over the ways, the rest empty (-1)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.feature_cache import hash_slots
    n_sets = c // assoc
    sets = np.asarray(hash_slots(jnp.asarray(pool), n_sets))
    keys = np.full(c, -1, np.int32)
    fill = np.zeros(n_sets, np.int64)
    for pid, s in zip(pool, sets):
        if fill[s] < assoc:
            keys[s * assoc + fill[s]] = pid
            fill[s] += 1
    return keys


def kernel_phase(seed: int) -> list:
    """Every Pallas kernel the GCN path can reach, compiled for the chip,
    against its jnp oracle at the config's widths.  Returns the failed
    checks (the caller fails after the training phase has run too)."""
    import functools

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.feature_cache import CacheConfig
    from repro.core.generation import probe_round_capacity
    from repro.graph.subgraph import slots_per_seed
    from repro.kernels import ref
    from repro.kernels.cache_gather import (cache_probe_compact_pallas,
                                            cache_probe_gather_pallas,
                                            cache_probe_tiered_pallas)
    from repro.kernels.fanout_mean import fanout_mean_pallas

    _check(jax.default_backend() == "tpu", "kernel phase runs on the TPU")
    cfg = get_config(ARCH)
    cc = CacheConfig.from_model(cfg)
    c, a, d = cc.n_rows, cc.assoc, cfg.gcn_in_dim
    l1 = cc._replace(mode="tiered", l1_rows=c // 8).validated()
    rng = np.random.default_rng(seed)
    pool = rng.choice(50 * c, size=2 * c, replace=False).astype(np.int32)

    def probes(shape):
        return np.where(rng.random(shape) < 0.5, rng.choice(pool, size=shape),
                        rng.integers(0, 50 * c, shape)).astype(np.int32)

    keys = _resident_keys(rng, c, a, pool)
    rows = rng.standard_normal((c, d), dtype=np.float32)
    l1_keys = _resident_keys(rng, l1.l1_rows, l1.l1_assoc, pool[::7])
    l1_rows = rng.standard_normal((l1.l1_rows, d), dtype=np.float32)
    b = 1024
    ids = probes(b * slots_per_seed(cfg.fanouts))       # one W=1 step

    failed = []

    def same(name, probe_shape, got, want):
        bad = []
        for i, (g, w) in enumerate(zip(got, want)):
            g, w = np.asarray(g), np.asarray(w)
            if g.shape != w.shape:
                bad.append(f"output {i}: shape {g.shape} != {w.shape}")
            elif not np.array_equal(g, w):
                diff = np.abs(g.astype(np.float64) - w.astype(np.float64))
                bad.append(f"output {i}: {int((g != w).sum())} of {g.size} "
                           f"elements differ, max |diff| {diff.max():.3g}")
        verdict = "bit-identical to its oracle" if not bad else "; ".join(bad)
        print(f"kernel {name}: {probe_shape} probes against {c} rows x {d}, "
              f"{verdict}")
        if bad:
            failed.append(f"{name} kernel matches its oracle bit for bit")

    same("gather", ids.shape,
         jax.jit(functools.partial(cache_probe_gather_pallas, assoc=a,
                                   interpret=False))(keys, rows, ids),
         jax.jit(functools.partial(ref.cache_probe_gather_ref, assoc=a))(
             keys, rows, ids))
    same("tiered", ids.shape,
         jax.jit(functools.partial(
             cache_probe_tiered_pallas, l1_assoc=l1.l1_assoc, l2_assoc=a,
             interpret=False))(l1_keys, l1_rows, keys, rows, ids),
         jax.jit(functools.partial(
             ref.cache_probe_tiered_ref, l1_assoc=l1.l1_assoc, l2_assoc=a))(
             l1_keys, l1_rows, keys, rows, ids))
    # the shard-probe response of a W=4 step at 256 seeds per worker
    w4 = 4
    cap = probe_round_capacity(256 * slots_per_seed(cfg.fanouts), w4, SLACK)
    recv = probes((w4, cap))
    recv[rng.random((w4, cap)) < 0.15] = -1
    hit_cap = cap // 2
    same("compact", recv.shape,
         jax.jit(functools.partial(cache_probe_compact_pallas, assoc=a,
                                   hit_cap=hit_cap, interpret=False))(
             keys, rows, recv),
         jax.jit(functools.partial(ref.cache_probe_compact_ref, assoc=a,
                                   hit_cap=hit_cap))(keys, rows, recv))
    # both GCN aggregations: hop-2 rows into hop 1, hidden rows into seeds
    for m, k, width in ((b * cfg.fanouts[0], cfg.fanouts[1], d),
                        (b, cfg.fanouts[0], cfg.gcn_hidden)):
        x = rng.standard_normal((m, k, width), dtype=np.float32)
        mask = rng.random((m, k)) < 0.8
        got = jax.jit(functools.partial(fanout_mean_pallas,
                                        interpret=False))(x, mask)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.fanout_mean_ref)(x, mask)
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        print(f"kernel fanout_mean [{m},{k},{width}]: max |diff| {err:.3g}")
        if not err <= 1e-5:
            failed.append(f"fanout_mean [{m},{k},{width}] within 1e-5 of "
                          f"its oracle")
    return failed


def check_rows(batch, feats) -> None:
    """Every masked-in row of the batch is its sampled id's table row and
    every masked-out row is zero: dedup, cache and shuffle served the
    right data."""
    import numpy as np
    seeds = np.asarray(batch.seeds)
    _check(np.array_equal(np.asarray(batch.x_seed), feats[seeds]),
           "seed rows equal the table's")
    n_rows = seeds.size
    for level, (hop, mask, x) in enumerate(
            zip(batch.hops, batch.masks, batch.x_hops)):
        hop, mask, x = np.asarray(hop), np.asarray(mask), np.asarray(x)
        _check(np.array_equal(x[mask], feats[hop[mask]]),
               f"hop-{level + 1} rows equal the table's")
        _check(not np.any(x[~mask]), f"hop-{level + 1} padding is zero")
        n_rows += int(mask.sum())
    print(f"feature rows: {n_rows} masked-in rows equal the NumPy table")


def train_phase(workers: int, batch_per_worker: int, steps: int,
                nodes: int = NODES):
    import jax
    import numpy as np
    from repro.graph.subgraph import slots_per_seed
    from repro.graph.synthetic import node_features
    from repro.launch.train import build_parser, train_gcn

    args = build_parser().parse_args([
        "--arch", ARCH, "--nodes", str(nodes),
        "--avg-degree", str(AVG_DEGREE), "--workers", str(workers),
        "--batch-per-worker", str(batch_per_worker),
        "--steps", str(steps), "--capacity-slack", str(SLACK),
        "--probe-hit-cap", str(HIT_CAP), "--seed", str(SEED),
        "--log-every", "1"])
    out = train_gcn(args)
    want = workers * batch_per_worker * slots_per_seed(out["batch"].fanouts)
    print(f"padded nodes/iter: {out['nodes_per_iter']} (expected {want})")
    print(f"dropped requests: {out['n_dropped']}")
    print(f"losses: {out['losses']}")
    _check(out["nodes_per_iter"] == want, "padded nodes per iteration")
    _check(out["n_dropped"] == 0, "no request dropped")
    _check(len(out["losses"]) == steps
           and bool(np.all(np.isfinite(out["losses"]))), "finite losses")
    feats = node_features(nodes, out["batch"].x_seed.shape[-1], SEED)
    check_rows(out["batch"], feats)
    for dev in jax.devices()[:workers]:
        stats = dev.memory_stats() or {}
        print(f"{dev}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return out


def check_placement(out, workers: int) -> None:
    """Each worker's CSR and feature-table shard lives on its own chip."""
    names = ("CSR indptr", "CSR indices", "feature table", "labels")
    for name, arr in zip(names, out["device_args"]):
        shards = arr.addressable_shards
        devs = {s.device.id for s in shards}
        starts = {s.index[0].start or 0 for s in shards}
        _check(len(shards) == workers and len(devs) == workers
               and len(starts) == workers
               and all(s.data.shape[0] * workers == arr.shape[0]
                       for s in shards),
               f"{name} holds one shard per chip")
        print(f"{name} {tuple(arr.shape)}: shards on devices "
              f"{sorted(devs)}, {tuple(shards[0].data.shape)} each")


def check_uncached(out) -> None:
    """The uncached generator, given the same seeds and rng, yields the
    cached run's last batch bit for bit."""
    import jax
    import numpy as np
    from repro.core.generation import make_generator_fn

    batch = out["batch"]
    gen = jax.jit(make_generator_fn(out["mesh"], fanouts=batch.fanouts,
                                    capacity_slack=out["capacity_slack"]))
    ref = gen(out["device_args"], out["batch_seeds"], out["batch_rng"])
    _check(int(np.asarray(ref.n_dropped).sum()) == 0,
           "the uncached generator dropped no request")
    pairs = ([("x_seed", batch.x_seed, ref.x_seed)]
             + [(f"hops[{i}]", a, b) for i, (a, b)
                in enumerate(zip(batch.hops, ref.hops))]
             + [(f"masks[{i}]", a, b) for i, (a, b)
                in enumerate(zip(batch.masks, ref.masks))]
             + [(f"x_hops[{i}]", a, b) for i, (a, b)
                in enumerate(zip(batch.x_hops, ref.x_hops))])
    for name, a, b in pairs:
        _check(np.array_equal(np.asarray(a), np.asarray(b)),
               f"cached and uncached {name} are bit-identical")
    print("cached vs uncached generator: features, ids and masks "
          "bit-identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + W=1 training (the default phase); "
                         "4: W=4 training on four chips, and nothing else")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"(platform {devices[0].platform}); compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        ids = {d.id for d in devices[:4]}
        _check(len(ids) == 4, "four distinct TPU devices")
        out = train_phase(workers=4, batch_per_worker=256, steps=3)
        check_placement(out, 4)
        check_uncached(out)
    else:
        failed = kernel_phase(SEED)
        out = train_phase(workers=1, batch_per_worker=1024, steps=6)
        _check(not failed, "; ".join(failed))
    print(f"setup_s {out['setup_s']:.1f} (graph, placement, first "
          f"generation); compile_s {clock.seconds:.1f} "
          f"({clock.cache_hits} persistent-cache hits); total_s "
          f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
