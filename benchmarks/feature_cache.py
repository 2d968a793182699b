"""Hot-node feature cache: wire-slot reduction vs cache size on Zipf skew,
and the three-way replicated / sharded / tiered placement sweep at equal
per-worker capacity.

Industrial graphs are power-law; a Zipf(1.1) request stream is the
canonical stand-in for the id mix a fanout sampler presents to the feature
shuffle.  PR 1's dedup already collapses duplicates *within* an iteration;
this benchmark measures what the cross-iteration cache tier removes on top:
the number of distinct ids that still go to their owner
(``FetchStats.n_unique`` summed over the run) as a function of
``cache_rows``, plus the steady-state hit rate and bytes saved.

With ``--workers > 1`` every TOTAL per-worker row budget is additionally
measured in **sharded** placement (cache-aware routing: ids probe the
worker whose CACHE shard owns them before falling through to the row
owner) and **tiered** placement (a replicated L1 head in front of the
sharded L2; equal-total split — the only power-of-two partition of a
power-of-two budget — is half L1, half L2).  Each replica of a replicated
cache converges on the same Zipf head, so total distinct capacity stays
~C; the sharded cache partitions the id-space and reaches W*C; the tiered
cache trades half the L2 capacity for serving the global head with ZERO
probe-round traffic.

Both probe-round modes are measured under BOTH wire formats
(``CacheConfig.wire``): a **dense** pass first (full [W, cap, D] response
block — it also observes ``CacheStats.probe_hit_peak``, the largest
per-destination hit count any holder produced), then a **compact** pass
with ``hit_cap`` sized to that peak plus a margin (mirroring the
launcher's calibration ladder).  ``probe_round_bytes`` is MEASURED — the
sum of ``FetchStats.probe_round_bytes``, i.e. the byte size of the
exchange buffers the compiled program actually ships — not an
occupied-slot estimate.  Gates ``main`` enforces at ``--workers > 1``:

  * compact probe bytes strictly below dense for BOTH sharded and tiered
    at every size, AND the reduction is at least the probe round's
    measured miss fraction (the compact claim: response bytes scale with
    hits, and on this stream most probe slots are not hits);
  * tiered compact probe bytes strictly below sharded compact at equal
    total rows (the L1 filter keeps the head off the round, so its hit
    peak — and therefore its payload — is smaller);
  * sharded hits strictly above replicated per size; the L1 serves
    >= 20% of tiered hits network-free.

    PYTHONPATH=src python -m benchmarks.feature_cache [--smoke] \
        [--out BENCH_feature_cache.json] [--workers N] [--iters K] \
        [--baseline benchmarks/baselines/feature_cache_smoke_w4.json]

Emits the ``name,us_per_call,derived`` CSV rows the benchmark harness
expects and (with ``--out``) a JSON artifact so CI can accumulate the perf
trajectory.  ``--baseline`` compares each (size, mode, wire) cell's
unique_reduction against a checked-in reference and fails on a >5%
relative regression (the nightly job's gate).  Acceptance anchors: at
``cache_rows=4096`` on Zipf(1.1) over >= 20 iterations the routed-unique
reduction vs cache-off is >= 30%, plus the wire gates above.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CACHE_SIZES = (1024, 4096, 16384)
SMOKE_SIZES = (1024, 4096)


_ZIPF_P = {}


def zipf_requests(rng, n_nodes: int, size: int, a: float = 1.1):
    """Bounded Zipf(a) ids over [0, n_nodes) (rank 0 = hottest node).

    Proper truncated-zeta sampling — folding ``rng.zipf`` mod n would
    redistribute the unbounded tail *uniformly*, burying the cacheable
    head under synthetic noise no real power-law graph has."""
    import numpy as np
    key = (n_nodes, a)
    if key not in _ZIPF_P:
        p = np.arange(1, n_nodes + 1, dtype=np.float64) ** -a
        _ZIPF_P[key] = p / p.sum()
    return rng.choice(n_nodes, size=size, p=_ZIPF_P[key]).astype(np.int32)


def measure(n_nodes: int, dim: int, requests: int, iters: int,
            cache_rows: int, *, admit: int = 2, assoc: int = 1,
            mode: str = "replicated", l1_rows: int = 0, l1_promote: int = 2,
            wire: str = "dense", hit_cap: int = 0,
            zipf_a: float = 1.1, seed: int = 0, workers: int = 1,
            time_it: bool = False) -> dict:
    """Run ``iters`` cached fetches over a Zipf stream; count routed uniques.

    Runs the REAL ``fetch_rows`` path under shard_map (the all_to_all
    routes between ``workers`` devices when more than one is forced), so
    ``FetchStats.n_unique`` is the number of ids that genuinely went — or,
    at W=1, would go — to their owner, and ``probe_round_bytes`` is the
    byte size of the buffers the probe round actually shipped.  Every
    worker draws its own iid Zipf stream (distinct per-worker request
    mixes are exactly what separates sharded from replicated placement).
    Counters are summed over ALL workers except ``probe_hit_peak``, which
    is max-reduced (it bounds the ``hit_cap`` a compact response needs).
    ``cache_rows`` is the main-tier (L2) size; tiered mode adds
    ``l1_rows`` replicated L1 slots, so total per-worker rows are
    ``cache_rows + l1_rows``.  ``wire``/``hit_cap`` select the probe-round
    response format (``CacheConfig.wire``; dense here by default so the
    sweep's first pass can observe the hit peak the compact pass needs).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.feature_cache import CacheConfig, init_cache_state
    from repro.core.generation import fetch_rows
    from repro.launch.mesh import make_mesh
    from .common import time_fn

    mesh = make_mesh((workers,), ("data",))
    rows_pw = -(-n_nodes // workers)
    rng = np.random.default_rng(seed)
    table = rng.standard_normal((workers * rows_pw, dim)).astype(np.float32)
    cached = cache_rows > 0
    cfg = CacheConfig(n_rows=cache_rows, admit=admit, assoc=assoc,
                      mode=mode, l1_rows=l1_rows if mode == "tiered" else 0,
                      l1_promote=l1_promote, wire=wire,
                      hit_cap=hit_cap).validated() if cached else None

    # each worker fetches rows for ITS OWN stream, so the fetched block is
    # per-worker data — it must leave the shard_map sharded, not stamped
    # replicated (check_vma=False would mask the mismatch silently)
    if cached:
        def worker(t, i, c):
            c = jax.tree.map(lambda a: a[0], c)
            out, c, fs, cs = fetch_rows(t, i[0], "data", cache=c,
                                        cache_cfg=cfg)
            c = jax.tree.map(lambda a: a[None], c)
            stats = jax.tree.map(lambda a: a[None], (fs, cs))
            return out[None], c, stats

        run = jax.jit(shard_map(
            worker, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")), check_vma=False))
        state = jax.device_put(
            init_cache_state(cfg, dim, workers),
            NamedSharding(mesh, P("data")))
    else:
        def worker_nc(t, i):
            out, fs = fetch_rows(t, i[0], "data", return_stats=True)
            return out[None], jax.tree.map(lambda a: a[None], fs)

        run = jax.jit(shard_map(
            worker_nc, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), check_vma=False))
        state = None

    table_j = jnp.asarray(table)
    # one iid Zipf stream PER WORKER per iteration, stacked [W, R] and
    # sharded so each worker presents its own request mix
    spec = NamedSharding(mesh, P("data"))
    streams = [jax.device_put(jnp.asarray(np.stack(
        [zipf_requests(rng, n_nodes, requests, zipf_a)
         for _ in range(workers)])), spec) for _ in range(iters)]
    sum_unique = 0
    sum_hits = 0
    sum_local_hits = 0
    sum_l1_hits = 0
    sum_bytes_saved = 0
    probe_round_bytes = 0
    probe_demoted = 0
    probe_hit_peak = 0
    dropped = 0
    for ids in streams:
        if cached:
            out, state, (fs, cs) = run(table_j, ids, state)
            sum_hits += int(np.asarray(cs.n_hits).sum())
            sum_l1_hits += int(np.asarray(cs.n_l1_hits).sum())
            sum_local_hits += int(np.asarray(cs.n_local_hits).sum())
            sum_bytes_saved += int(np.asarray(cs.bytes_saved).sum())
            probe_demoted += int(np.asarray(cs.n_probe_demoted).sum())
            probe_hit_peak = max(probe_hit_peak,
                                 int(np.asarray(cs.probe_hit_peak).max()))
            # MEASURED: the byte size of the buffers every worker actually
            # shipped on the shard-probe all_to_all this iteration
            probe_round_bytes += int(np.asarray(fs.probe_round_bytes).sum())
        else:
            out, fs = run(table_j, ids)
        sum_unique += int(np.asarray(fs.n_unique).sum())
        dropped += int(np.asarray(fs.n_dropped).sum())
    rec = {
        "cache_rows": cache_rows,
        "l1_rows": l1_rows if (cached and mode == "tiered") else 0,
        "total_rows": cache_rows + (l1_rows if (cached and mode == "tiered")
                                    else 0),
        "admit": admit,
        "assoc": assoc,
        "mode": mode if cached else None,
        "wire": (wire if (cached and mode in ("sharded", "tiered")
                          and workers > 1) else None),
        "hit_cap": hit_cap if cached else 0,
        "sum_n_unique": sum_unique,
        "sum_hits": sum_hits,
        "sum_l1_hits": sum_l1_hits,
        "sum_local_hits": sum_local_hits,
        "sum_shard_hits": sum_hits - sum_local_hits - sum_l1_hits,
        "sum_bytes_saved": sum_bytes_saved,
        "probe_round_bytes": probe_round_bytes,
        "probe_demoted": probe_demoted,
        "probe_hit_peak": probe_hit_peak,
        "dropped": dropped,
        "hit_rate": sum_hits / max(sum_hits + sum_unique, 1),
    }
    if time_it:
        if cached:
            rec["us_per_fetch"] = time_fn(
                lambda: run(table_j, streams[0], state))
        else:
            rec["us_per_fetch"] = time_fn(lambda: run(table_j, streams[0]))
    return rec


def calibrated_hit_cap(peak: int) -> int:
    """Compact payload bound from a dense pass's observed hit peak.

    Peak plus a ~12% skew margin (floored at 8 rows): the compact pass
    must not demote on the same stream the peak was measured on, but a
    bound tracking the peak tightly is exactly what makes the response
    scale with hits."""
    return max(peak + max(peak // 8, 8), 1)


def sweep(*, smoke: bool = False, workers: int = 1, iters: int = None,
          seed: int = 0, assoc: int = 2, time_it: bool = False) -> dict:
    """Three-way placement sweep at EQUAL total per-worker rows, each
    probe-round mode under both wire formats.

    Every swept size ``c`` is the TOTAL per-worker row budget: replicated
    and sharded spend all of it on their single tier; tiered splits it
    half L1 / half L2 (the only power-of-two partition of a power-of-two
    budget — both tiers hash with the top-bits trick, so both must be
    powers of two).  Sharded/tiered cells run twice: a dense pass that
    also observes the per-destination hit peak, then a compact pass with
    ``hit_cap = calibrated_hit_cap(peak)`` — the same peak-plus-margin
    policy the launcher's ladder converges to."""
    n_nodes = 20_000 if smoke else 200_000
    dim = 32 if smoke else 128
    requests = 4_096 if smoke else 16_384
    iters = iters or (20 if smoke else 50)
    sizes = SMOKE_SIZES if smoke else CACHE_SIZES
    base = measure(n_nodes, dim, requests, iters, 0, seed=seed,
                   workers=workers, time_it=time_it)
    results = [base]
    modes = (("replicated", "sharded", "tiered") if workers > 1
             else ("replicated",))
    for c in sizes:
        for mode in modes:
            l2 = c // 2 if mode == "tiered" else c
            l1 = c // 2 if mode == "tiered" else 0
            rec = measure(n_nodes, dim, requests, iters, l2, seed=seed,
                          assoc=assoc, mode=mode, l1_rows=l1,
                          workers=workers, time_it=time_it)
            rec["unique_reduction"] = 1.0 - rec["sum_n_unique"] / max(
                base["sum_n_unique"], 1)
            results.append(rec)
            if rec["wire"] is None:
                continue        # no probe round -> nothing to compact
            hc = calibrated_hit_cap(rec["probe_hit_peak"])
            crec = measure(n_nodes, dim, requests, iters, l2, seed=seed,
                           assoc=assoc, mode=mode, l1_rows=l1,
                           wire="compact", hit_cap=hc,
                           workers=workers, time_it=time_it)
            crec["unique_reduction"] = 1.0 - crec["sum_n_unique"] / max(
                base["sum_n_unique"], 1)
            results.append(crec)
    return {
        "benchmark": "feature_cache",
        "zipf_a": 1.1,
        "n_nodes": n_nodes,
        "dim": dim,
        "requests_per_iter": requests,
        "iters": iters,
        "workers": workers,
        "assoc": assoc,
        "results": results,
    }


def _row_name(r: dict) -> str:
    name = f"feature_cache_rows_{r['total_rows']}"
    if r.get("mode"):
        name += f"_{r['mode']}"
    if r.get("wire"):
        name += f"_{r['wire']}"
    return name


def check_baseline(rec: dict, baseline: dict, tol: float = 0.05) -> list:
    """Compare each (total_rows, mode, wire) cell's unique_reduction
    against a checked-in baseline; return failure strings for any cell
    whose reduction fell more than ``tol`` RELATIVE (the nightly
    regression gate).  Cells missing on either side are skipped — adding
    a new size, mode, or wire must not fail the old baseline."""
    def key(r):
        return (r.get("total_rows"), r.get("mode"), r.get("wire"))

    have = {key(r): r for r in rec["results"] if r.get("mode")}
    failures = []
    for b in baseline.get("results", []):
        if not b.get("mode") or "unique_reduction" not in b:
            continue
        now = have.get(key(b))
        if now is None:
            continue
        floor = b["unique_reduction"] * (1.0 - tol)
        if now["unique_reduction"] < floor:
            failures.append(
                f"{_row_name(b)}: unique_reduction "
                f"{now['unique_reduction']:.3f} < baseline "
                f"{b['unique_reduction']:.3f} - {tol:.0%}")
    return failures


def bench() -> list:
    """Harness entry (benchmarks.run): smoke-size sweep, CSV rows."""
    rec = sweep(smoke=True)
    rows = []
    for r in rec["results"]:
        derived = (f"routed_unique={r['sum_n_unique']}"
                   f",hit_rate={r['hit_rate']:.3f}")
        if "unique_reduction" in r:
            derived += f",unique_reduction={r['unique_reduction']:.3f}"
        rows.append((_row_name(r), float(r.get("us_per_fetch", 0.0)), derived))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes (the CI configuration)")
    ap.add_argument("--workers", type=int, default=1,
                    help="forced host devices; >1 exercises the real "
                         "all_to_all routing AND the sharded-mode sweep")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--assoc", type=int, default=2, choices=[1, 2, 4])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time", action="store_true",
                    help="also time each fetch variant")
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--baseline", default=None,
                    help="checked-in baseline JSON; fail if any mode's "
                         "unique_reduction regresses >5%% relative")
    args = ap.parse_args()
    if args.workers > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.workers} "
            + os.environ.get("XLA_FLAGS", ""))

    rec = sweep(smoke=args.smoke, workers=args.workers, iters=args.iters,
                seed=args.seed, assoc=args.assoc, time_it=args.time)
    print("name,us_per_call,derived")
    for r in rec["results"]:
        red = r.get("unique_reduction")
        line = (f"{_row_name(r)},"
                f"{r.get('us_per_fetch', 0.0):.1f},"
                f"routed_unique={r['sum_n_unique']}"
                f",hit_rate={r['hit_rate']:.3f}")
        if red is not None:
            line += f",unique_reduction={red:.3f}"
        if r.get("wire"):
            line += (f",wire={r['wire']}"
                     f",probe_round_bytes={r['probe_round_bytes']}")
            if r["wire"] == "compact":
                line += (f",hit_cap={r['hit_cap']}"
                         f",demoted={r['probe_demoted']}")
        if r.get("mode") == "tiered":
            line += (f",l1_hit_share="
                     f"{r['sum_l1_hits'] / max(r['sum_hits'], 1):.3f}")
        print(line)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"wrote {args.out}", file=sys.stderr)
    failed = False
    at4096 = [r for r in rec["results"]
              if r["cache_rows"] == 4096 and r.get("mode") == "replicated"]
    if at4096 and at4096[0].get("unique_reduction", 0.0) < 0.30:
        print("WARNING: <30% routed-unique reduction at cache_rows=4096",
              file=sys.stderr)
        failed = True
    if args.workers > 1:
        cells = {}
        for r in rec["results"]:
            if r.get("mode"):
                cells[(r["total_rows"], r["mode"], r.get("wire"))] = r
        for c in sorted({k[0] for k in cells}):
            rep = cells.get((c, "replicated", None))
            sh_d = cells.get((c, "sharded", "dense"))
            sh_c = cells.get((c, "sharded", "compact"))
            ti_d = cells.get((c, "tiered", "dense"))
            ti_c = cells.get((c, "tiered", "compact"))
            # the sharded claim: strictly more unique hits than replication
            # at EQUAL total per-worker rows, for every swept size
            if rep and sh_d and sh_d["sum_hits"] <= rep["sum_hits"]:
                print(f"WARNING: sharded hits {sh_d['sum_hits']} <= "
                      f"replicated {rep['sum_hits']} at total_rows={c}",
                      file=sys.stderr)
                failed = True
            # the compact-wire claim, per probe-round mode: MEASURED bytes
            # strictly below dense, by at least the probe round's miss
            # fraction (the response is the dominant direction, and only
            # its hit slots carry data)
            for mode, d, k in (("sharded", sh_d, sh_c),
                               ("tiered", ti_d, ti_c)):
                if not (d and k):
                    continue
                if k["probe_round_bytes"] >= d["probe_round_bytes"]:
                    print(f"WARNING: {mode} compact probe bytes "
                          f"{k['probe_round_bytes']} >= dense "
                          f"{d['probe_round_bytes']} at total_rows={c}",
                          file=sys.stderr)
                    failed = True
                # ids the probe round carried = hits it served (L1 hits
                # never enter it) + misses; the miss fraction of THOSE
                carried = (d["sum_hits"] - d["sum_l1_hits"]
                           + d["sum_n_unique"])
                miss_frac = d["sum_n_unique"] / max(carried, 1)
                reduction = 1.0 - (k["probe_round_bytes"]
                                   / max(d["probe_round_bytes"], 1))
                if reduction < miss_frac:
                    print(f"WARNING: {mode} compact reduction "
                          f"{reduction:.1%} < probe-round miss fraction "
                          f"{miss_frac:.1%} at total_rows={c}",
                          file=sys.stderr)
                    failed = True
            # the tiered claim: the L1 head keeps distinct ids OFF the
            # probe round, so its hit peak — and therefore its compact
            # payload — stays strictly below sharded at equal total rows,
            # with the L1 serving >= 20% of all hits without any network
            if sh_c and ti_c:
                if ti_c["probe_round_bytes"] >= sh_c["probe_round_bytes"]:
                    print(f"WARNING: tiered compact probe bytes "
                          f"{ti_c['probe_round_bytes']} >= sharded "
                          f"{sh_c['probe_round_bytes']} at total_rows={c}",
                          file=sys.stderr)
                    failed = True
            if ti_d:
                l1_share = ti_d["sum_l1_hits"] / max(ti_d["sum_hits"], 1)
                if l1_share < 0.20:
                    print(f"WARNING: L1 serves only {l1_share:.1%} of tiered "
                          f"hits at total_rows={c} (need >= 20%)",
                          file=sys.stderr)
                    failed = True
    if args.baseline:
        with open(args.baseline) as f:
            base_rec = json.load(f)
        for msg in check_baseline(rec, base_rec):
            print(f"REGRESSION: {msg}", file=sys.stderr)
            failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
