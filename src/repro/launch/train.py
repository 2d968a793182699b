"""End-to-end training driver.

Two modes, selected by --arch:

* ``graphgen-gcn`` (the paper) or any other GNN arch (``graphgen-gat``):
  synthetic power-law graph -> coordinator partitioning -> balance table
  -> synchronized distributed subgraph generation + in-memory GNN
  training (the GraphGen+ pipeline), with checkpoint/restart and optional
  failure injection.

* any LM arch id: reduced-config training on synthetic token batches using
  the same substrate (AdamW, microbatching, checkpointing).

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch graphgen-gcn --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --steps 10 --smoke
    REPRO_FORCE_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
        --arch graphgen-gcn --steps 30 --workers 8
"""
import os
if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['REPRO_FORCE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse        # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np     # noqa: E402

from ..configs import get_config, smoke_config          # noqa: E402
from ..core.balance import balance_table                # noqa: E402
from ..core.config import TrainConfig                   # noqa: E402
from ..core.generation import make_distributed_generator  # noqa: E402
from ..core.partition import partition_edges            # noqa: E402
from ..core.pipeline import make_pipelined_step         # noqa: E402
from ..graph.synthetic import node_features, node_labels, powerlaw_graph  # noqa: E402
from ..models import zoo                                # noqa: E402
from ..train import checkpoint as ckpt                  # noqa: E402
from ..train.optimizer import adam_update, init_adam    # noqa: E402
from ..train.train_loop import init_state, make_train_step  # noqa: E402
from .compile_cache import enable_compile_cache        # noqa: E402
from .mesh import make_mesh                             # noqa: E402


#: ascending slack ladder probed by the drop-aware capacity calibration
SLACK_LADDER = (0.25, 0.5, 1.0, 1.5, 2.0)
#: calibration batches per rung — a single probe has no safety margin
#: against seed/rng draws with more uniques per destination
CALIBRATION_PROBES = 3
#: ascending hit-cap ladder (fractions of the probe-round capacity)
#: probed by the compact-wire calibration; a rung is accepted when no
#: probe demotes a hit, and a run that demotes on EVERY rung falls back
#: to the dense wire (the dense-fallback rung)
HIT_CAP_LADDER = (0.125, 0.25, 0.5)


def calibrate_capacity_slack(mesh, device_args, fanouts, probes,
                             ladder=SLACK_LADDER, cache_cfg=None) -> float:
    """Drop-aware capacity autotuning (ROADMAP item).

    ``probes`` is a list of ``(seeds, rng)`` calibration batches; the
    graph/table placement in ``device_args`` is shared across the whole
    ladder (slack only changes the compiled program, not the data).
    Returns the smallest slack whose ``SubgraphBatch.n_dropped`` is zero
    over EVERY probe — the all_to_all exchange buffers then carry no more
    static padding than the workload needs, with the multi-probe pass
    standing in for a worst-case bound.

    With ``cache_cfg`` the ladder probes the CACHED generator, and every
    rung starts from a freshly initialized (cold) cache: the heaviest
    owner-fetch traffic is the cold-start miss burst, and a cache warmed
    by a previous rung would understate it — the chosen slack would then
    drop requests on the real run's first iterations.  (Within a rung the
    cache threads across the probes, exactly as the real run warms up.)
    """
    from ..core.feature_cache import init_cache_state
    from ..core.generation import make_generator_fn
    from jax.sharding import NamedSharding, PartitionSpec as P

    w = mesh.shape["data"]
    feat_dim = device_args[2].shape[1]     # the placed [W*rows, D] table
    cached = cache_cfg is not None and cache_cfg.n_rows > 0
    for slack in ladder:
        gen_fn = jax.jit(make_generator_fn(
            mesh, fanouts=fanouts, capacity_slack=slack,
            cache_cfg=cache_cfg if cached else None))
        if cached:
            # COLD cache per rung (see docstring); init_cache_state is
            # mode-polymorphic (flat state or tiered (l1, l2) pytree)
            cache = jax.device_put(
                init_cache_state(cache_cfg, feat_dim, w),
                NamedSharding(mesh, P("data")))
        dropped = 0
        for seeds, rng in probes:
            if cached:
                batch, cache = gen_fn(device_args, seeds, rng, cache)
            else:
                batch = gen_fn(device_args, seeds, rng)
            dropped += int(np.asarray(batch.n_dropped).sum())
        if dropped == 0:
            return slack
        print(f"calibration: slack={slack} dropped {dropped} requests "
              f"over {len(probes)} probes")
    print(f"calibration: even slack={ladder[-1]} drops requests; keeping it")
    return ladder[-1]


def calibrate_probe_hit_cap(mesh, device_args, fanouts, probes, slack,
                            cache_cfg, ladder=HIT_CAP_LADDER):
    """Compact-wire hit-cap calibration (the probe-compaction ROADMAP item).

    Probes an ascending ladder of ``hit_cap`` rungs — fractions of the
    probe-round capacity the compiled fetch will actually use
    (``generation.probe_round_capacity``) — and returns the ``CacheConfig``
    of the smallest rung whose probes report ZERO demoted hits
    (``SubgraphBatch.n_probe_demoted``): the compact probe response then
    ships the fewest payload rows that still carry every hit the cache
    produced during calibration.  The cache warms WITHIN a rung (state
    threads across the probes), so later probes see warm-ish hit counts;
    steady-state hit excursions beyond the calibrated bound only demote
    (lost hit opportunity, logged by the training loop), never corrupt.

    If every rung demotes, the DENSE wire is the fallback rung: the hit
    population is too large for a payload bound to pay off, so the run
    keeps the format that can never demote.
    """
    from ..core.feature_cache import init_cache_state
    from ..core.generation import make_generator_fn, probe_round_capacity
    from ..graph.subgraph import slots_per_seed
    from jax.sharding import NamedSharding, PartitionSpec as P

    w = mesh.shape["data"]
    feat_dim = device_args[2].shape[1]
    b = probes[0][0].shape[1]              # seeds are [W, b]
    n_requests = b * slots_per_seed(fanouts)
    cap = probe_round_capacity(n_requests, w, slack)
    for frac in ladder:
        hc = max(int(cap * frac), 1)
        cfg = cache_cfg._replace(wire="compact", hit_cap=hc)
        gen_fn = jax.jit(make_generator_fn(
            mesh, fanouts=fanouts, capacity_slack=slack, cache_cfg=cfg))
        cache = jax.device_put(init_cache_state(cfg, feat_dim, w),
                               NamedSharding(mesh, P("data")))
        demoted = 0
        for seeds, rng in probes:
            batch, cache = gen_fn(device_args, seeds, rng, cache)
            demoted += int(np.asarray(batch.n_probe_demoted).sum())
        if demoted == 0:
            print(f"probe hit-cap auto-sized to {hc} rows/destination "
                  f"({frac:.0%} of the {cap}-slot probe round; override "
                  f"with --probe-hit-cap)")
            return cfg
        print(f"hit-cap calibration: hit_cap={hc} demoted {demoted} hits "
              f"over {len(probes)} probes")
    print(f"hit-cap calibration: even {ladder[-1]:.0%} of the probe round "
          f"demotes hits; falling back to the dense wire")
    return cache_cfg._replace(wire="dense", hit_cap=0)


def warm_capacity(miss_peak: int, w: int, slack: float, rows: int,
                  margin: int = 8) -> int:
    """Steady-state owner-exchange capacity from a warm miss measurement.

    ``miss_peak`` is the largest per-worker routed-miss count observed
    over the warm window; the per-destination capacity only needs to
    carry those misses (not the full pre-cache request count), spread
    over ``w`` destinations.  The skew allowance floors at 2x regardless
    of the calibrated ``slack``: steady-state miss counts are small, so
    their per-destination peaks are relatively spikier than the cold
    request mix the slack was calibrated on (and the training loop's
    drop-rollback still guards the residual risk).  Clamped to ``rows``
    (a destination can never serve more distinct ids than it owns)."""
    cap = int(-(-miss_peak // max(w, 1)) * max(slack, 2.0)) + margin
    return max(min(cap, rows), 1)


def train_gcn(args) -> dict:
    """Train the GNN of ``args.arch`` (init and loss from ``zoo.build``)
    on a synthetic power-law graph.

    Returns the losses, padded nodes per iteration, wall and set-up
    seconds, the slack used and the requests dropped over every trained
    batch; on the device feature store also the last trained batch with
    the seeds and rng that generated it, the placed ``device_args`` and
    the mesh."""
    import dataclasses
    t_setup = time.perf_counter()
    w = args.workers
    mesh = make_mesh((w,), ("data",))
    cfg = get_config(args.arch)
    if args.fanouts:
        try:
            fo = tuple(int(k) for k in args.fanouts.split(","))
        except ValueError:
            raise SystemExit(
                f"--fanouts expects comma-separated ints (e.g. 15,10,5), "
                f"got {args.fanouts!r}")
        if not fo or any(k < 1 for k in fo):
            raise SystemExit(f"--fanouts entries must be >= 1, got {fo}")
        cfg = dataclasses.replace(cfg, fanouts=fo)
    if args.cache_rows is not None:
        cfg = dataclasses.replace(cfg, cache_rows=args.cache_rows)
    if args.cache_admit is not None:
        cfg = dataclasses.replace(cfg, cache_admit=args.cache_admit)
    if args.cache_assoc is not None:
        cfg = dataclasses.replace(cfg, cache_assoc=args.cache_assoc)
    if args.cache_mode is not None:
        cfg = dataclasses.replace(cfg, cache_mode=args.cache_mode)
    if args.l1_rows is not None:
        cfg = dataclasses.replace(cfg, cache_l1_rows=args.l1_rows)
    if args.l1_promote is not None:
        cfg = dataclasses.replace(cfg, cache_l1_promote=args.l1_promote)
    if args.probe_wire is not None:
        cfg = dataclasses.replace(cfg, cache_wire=args.probe_wire)
    if args.probe_hit_cap is not None:
        cfg = dataclasses.replace(cfg, cache_hit_cap=args.probe_hit_cap)
    if args.feature_store is not None:
        cfg = dataclasses.replace(cfg, feature_store=args.feature_store)
    if args.host_gather_depth is not None:
        cfg = dataclasses.replace(cfg,
                                  host_gather_depth=args.host_gather_depth)
    if args.smoke:
        cfg = smoke_config(cfg)
    fanouts = cfg.fanouts
    host = cfg.feature_store == "host"
    if host and args.warm_recalibrate:
        raise SystemExit("--warm-recalibrate shrinks the owner-exchange "
                         "buffers, which --feature-store host replaces "
                         "with the L3 staging path — drop the flag")
    from ..core.feature_cache import CacheConfig
    cache_cfg = CacheConfig.from_model(cfg)
    cached = cache_cfg is not None

    graph = powerlaw_graph(args.nodes, avg_degree=args.avg_degree,
                           n_hot=max(args.nodes // 1000, 1), seed=args.seed)
    part = partition_edges(graph, w)                       # step 1
    feats = node_features(graph.n_nodes, cfg.gcn_in_dim, args.seed,
                          features_on_host=host)
    labels = node_labels(graph.n_nodes, cfg.n_classes, args.seed)
    table = balance_table(np.arange(graph.n_nodes), w, args.seed)  # step 2

    b = args.batch_per_worker
    rngs = jax.random.split(jax.random.PRNGKey(args.seed + 1), args.steps + 1)

    def seeds_for(t):
        sw = table.per_worker
        cols = (np.arange(b) + t * b) % sw.shape[1]
        return jnp.asarray(sw[:, cols])

    # --- profile-driven autotune: one trace + offline search replaces
    # the serial calibration ladders; the ladders survive below as the
    # fallback path the validator rolls back to on rejection ------------
    autotuned = None
    if args.autotune:
        from .autotune import autotune_gcn, candidate_cache_cfg
        at_rngs = jax.random.split(jax.random.PRNGKey(args.seed + 2),
                                   max(args.autotune_steps, 1))
        res = autotune_gcn(
            mesh, part, feats, labels, fanouts=fanouts,
            cache_cfg=cache_cfg, feature_store=cfg.feature_store,
            batch_per_worker=b, seeds_for=seeds_for, rngs=at_rngs,
            steps=args.autotune_steps,
            slack=(args.capacity_slack or cfg.capacity_slack or 2.0))
        if res.accepted:
            autotuned = res
            cand = res.candidate
            cfg = cfg.with_candidate(cand)
            fanouts = cfg.fanouts
            if cached:
                cache_cfg = candidate_cache_cfg(cache_cfg, cand)
            print(f"autotune: accepted (measured "
                  f"{res.measured_step_s * 1e3:.1f} ms/step warm)")
        else:
            print(f"autotune: WARNING — falling back to the calibration "
                  f"ladders ({res.reason})")

    need_slack_cal = (args.capacity_slack is None
                      and cfg.capacity_slack is None and w > 1
                      and not host and autotuned is None)
    # the compact probe wire needs a hit_cap; calibrate one unless the
    # config pins it or --probe-hit-cap was given (any explicit value —
    # including 0, which selects the uncalibrated half-capacity auto
    # bound — skips the ladder; replicated mode and W == 1 run no probe
    # round, so there is nothing to compact)
    need_hit_cap = (cached and w > 1 and cache_cfg.mode != "replicated"
                    and cache_cfg.wire == "compact"
                    and cache_cfg.hit_cap == 0
                    and args.probe_hit_cap is None
                    and not host and autotuned is None)
    cal_args = probes = None
    if need_slack_cal or need_hit_cap:
        # place the graph+tables once; every ladder rung (slack AND
        # hit-cap) only re-jits against the same placement
        _, cal_args = make_distributed_generator(
            mesh, part, feats, labels, fanouts=fanouts)
        probes = [(seeds_for(t), rngs[t]) for t in range(CALIBRATION_PROBES)]
    if args.capacity_slack is not None:
        slack = args.capacity_slack
    elif cfg.capacity_slack is not None:
        slack = cfg.capacity_slack       # config pins it: no calibration
    elif host:
        # host mode replaces the owner exchange with the L3 staging path,
        # whose default staging size never drops — the ladder would probe
        # a device-resident generator this run will not compile
        slack = 2.0
        if w > 1:
            print("capacity_slack fixed at 2.0 (--feature-store host "
                  "skips the drop-aware ladder: misses stage to the L3 "
                  "store instead of the owner exchange)")
    elif w == 1:
        slack = 2.0      # W=1 fetch is a local gather: capacity never binds
    else:
        # probing the CACHED generator (cold cache per rung) so the slack
        # covers the configured path's cold-start miss traffic
        slack = calibrate_capacity_slack(mesh, cal_args, fanouts, probes,
                                         cache_cfg=cache_cfg)
        print(f"capacity_slack auto-sized to {slack} "
              f"(override with --capacity-slack)")
    if need_hit_cap:
        cache_cfg = calibrate_probe_hit_cap(mesh, cal_args, fanouts, probes,
                                            slack, cache_cfg)
    del cal_args, probes

    gen_out = make_distributed_generator(                  # step 3
        mesh, part, feats, labels, fanouts=fanouts, capacity_slack=slack,
        cache_cfg=cache_cfg, feature_store=cfg.feature_store,
        host_gather_depth=cfg.host_gather_depth,
    )
    store = None
    cache = None
    if host and cached:
        gen_fn, device_args, store, cache = gen_out
    elif host:
        gen_fn, device_args, store = gen_out
    elif cached:
        gen_fn, device_args, cache = gen_out
    else:
        gen_fn, device_args = gen_out
    if host:
        print(f"L3 host feature store: {feats.shape[0]}x{feats.shape[1]} "
              f"f32 table ({feats.nbytes / 1e6:.1f} MB) in host RAM, "
              f"gather depth {cfg.host_gather_depth} "
              f"({'overlapped' if cfg.host_gather_depth == 2 else 'synchronous'})")
    if cached:
        line = (f"hot-node cache: {cache_cfg.n_rows} rows/worker "
                f"({cache_cfg.assoc}-way, {cache_cfg.mode}), "
                f"admit-after-{cache_cfg.admit}")
        if cache_cfg.mode == "tiered":
            line += (f" + {cache_cfg.l1_rows}-row replicated L1 "
                     f"(promote-after-{cache_cfg.l1_promote})")
        if cache_cfg.mode != "replicated" and w > 1:
            line += f", {cache_cfg.wire} probe wire"
            if cache_cfg.wire == "compact" and cache_cfg.hit_cap:
                line += f" (hit_cap {cache_cfg.hit_cap})"
        print(line)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       checkpoint_every=args.ckpt_every)
    model = zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    opt = init_adam(params)

    def train_fn(params, opt, batch):                      # step 4
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt, _ = adam_update(tcfg, params, grads, opt)
        return params, opt, loss

    start = 0
    if args.resume and ckpt.latest_step(args.ckpt_dir) is not None:
        start = ckpt.latest_step(args.ckpt_dir)
        params, opt = ckpt.restore(args.ckpt_dir, start, (params, opt))
        print(f"resumed from step {start}")

    step = None
    consume_step = None
    train_step = jax.jit(train_fn)
    pending = None
    if host:
        from ..core.host_store import empty_admit
        from ..core.pipeline import make_host_consume_step
        consume_step = jax.jit(make_host_consume_step(train_fn))
    else:
        step = jax.jit(make_pipelined_step(gen_fn, train_fn, cached=cached))
    # batch t comes from seeds_for(t)/rngs[t] — a resumed run must prime the
    # pipeline at `start`, not at 0.  Host mode keeps the cache OUT of the
    # carry: the split dispatch (gen / issue / consume) threads it through
    # the generation call directly.
    if host and cached:
        adm_ids, adm_rows = empty_admit(w, feats.shape[1])
        batch, cache, req = gen_fn(device_args, seeds_for(start),
                                   rngs[start], cache, adm_ids, adm_rows)
        carry = (params, opt, batch, req)
        pending = store.issue(req.ids)
    elif host:
        batch, req = gen_fn(device_args, seeds_for(start), rngs[start])
        carry = (params, opt, batch, req)
        pending = store.issue(req.ids)
    elif cached:
        batch, cache = gen_fn(device_args, seeds_for(start), rngs[start], cache)
        carry = (params, opt, batch, cache)
    else:
        batch = gen_fn(device_args, seeds_for(start), rngs[start])
        carry = (params, opt, batch)
    losses = []
    n_dropped = 0             # requests dropped over every trained batch
    miss_peak = 0
    wide_step = None          # pre-recalibration step, kept for rollback
    # the first batches carry the cold-start miss burst the cache exists to
    # eliminate — measuring them would size the "warm" buffers to the cold
    # peak; only the second half of the warm window counts
    warm_from = start + max(args.warm_recalibrate // 2, 1)
    t0 = time.perf_counter()
    for t in range(start, args.steps):
        if cached and args.warm_recalibrate and t >= warm_from:
            miss_peak = max(miss_peak, int(np.asarray(
                carry[2].n_cache_misses).max()))
        # rollback check FIRST: when it fires, carry[2] was generated by
        # the SHRUNKEN generator (the recalibration below installs the
        # shrink only after this point, so a drop in a wide-generated
        # batch can never be misattributed to the shrink)
        if (wide_step is not None
                and int(np.asarray(carry[2].n_dropped).sum()) > 0):
            # the shrunken buffers dropped requests (a miss-rate excursion
            # beyond the warm sample) — zero-filled features must never
            # train, so regenerate THIS batch at the calibrated width and
            # roll the step back for good.  (The regeneration re-offers
            # the batch's served rows to the cache — a second admission
            # tick for those ids, harmless: admission is a heuristic and
            # rows stay verbatim table copies.)
            step = wide_step
            wide_step = None
            batch, cache_now = wide_gen(device_args, seeds_for(t), rngs[t],
                                        carry[3])
            carry = (carry[0], carry[1], batch, cache_now)
            print(f"step {t}: shrunken capacity dropped requests — "
                  f"regenerated the batch and rolled back to the "
                  f"calibrated width")
        n_dropped += int(np.asarray(carry[2].n_dropped).sum())
        if (args.warm_recalibrate and cached and w > 1
                and t == start + args.warm_recalibrate
                and t + 1 < args.steps):
            # cache-aware capacity shrink: by now the cache serves the hot
            # head, so the owner exchange only carries steady-state misses
            # — re-jit the generator with buffers sized to the warm peak
            # (the cold-start burst is behind us; the cache state carries
            # over, so the miss rate will not rebound)
            from ..core.generation import make_generator_fn
            rows_pw = device_args[2].shape[0] // w
            new_cap = warm_capacity(miss_peak, w, slack, rows_pw)
            wide_step, wide_gen = step, gen_fn
            gen_fn = jax.jit(make_generator_fn(
                mesh, fanouts=fanouts, capacity_slack=slack,
                cache_cfg=cache_cfg, fetch_capacity=new_cap))
            step = jax.jit(make_pipelined_step(gen_fn, train_fn,
                                               cached=True))
            print(f"warm re-calibration at step {t}: owner-exchange "
                  f"capacity -> {new_cap} slots/destination "
                  f"(peak warm per-worker misses {miss_peak})")
        if t + 1 < args.steps:
            if host:
                # split dispatch: collect batch t's landed gather, queue
                # gen t+1 (admitting the landed rows), issue ITS gather,
                # then dispatch patch+train of batch t — the gather's
                # host work overlaps the consume program's compute
                landed = pending.rows()
                if cached:
                    batch, cache, req = gen_fn(device_args,
                                               seeds_for(t + 1),
                                               rngs[t + 1], cache,
                                               carry[3].ids, landed)
                else:
                    batch, req = gen_fn(device_args, seeds_for(t + 1),
                                        rngs[t + 1])
                pending = store.issue(req.ids)
                p, o, loss = consume_step(carry[0], carry[1], carry[2],
                                          carry[3], landed)
                carry = (p, o, batch, req)
            else:
                carry, loss = step(carry, device_args, seeds_for(t + 1),
                                   rngs[t + 1])
        elif host:
            # drain: the last batch still has staged feature holes
            p, o, loss = consume_step(carry[0], carry[1], carry[2],
                                      carry[3], pending.rows())
            carry = (p, o) + carry[2:]
        else:
            # nothing left to pre-generate: train-only final step (the same
            # redundant-generation fix pipelined_loop carries)
            p, o, loss = train_step(carry[0], carry[1], carry[2])
            carry = (p, o) + carry[2:]
        losses.append(float(loss))
        if (t + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, t + 1, (carry[0], carry[1]),
                      keep=tcfg.keep_checkpoints)
        if (t + 1) % args.log_every == 0:
            line = f"step {t+1}: loss={losses[-1]:.4f}"
            nb = carry[2]
            if cached:
                line += f" cache_hit_rate={nb.cache_hit_rate():.3f}"
            dropped = int(np.asarray(nb.n_dropped).sum())
            if dropped:
                line += f" DROPPED={dropped}"
            if cached and nb.n_probe_demoted is not None:
                demoted = int(np.asarray(nb.n_probe_demoted).sum())
                if demoted:
                    # a hit excursion beyond the calibrated hit_cap: those
                    # ids were owner-fetched instead (lost hit, not a bug)
                    line += f" demoted={demoted}"
            print(line)
    if pending is not None:
        # a zero-step run (resume landing exactly at args.steps) primes the
        # gather but never reaches the loop's drain; rows() memoizes, so on
        # every other path this hits the already-landed buffer for free
        pending.rows()
    if args.export_serve:
        if not cached:
            raise SystemExit("--export-serve checkpoints params + the warm "
                             "cache state; this run has no cache "
                             "(--cache-rows 0)")
        # device mode threads the cache through the pipelined carry; host
        # mode keeps it in the local variable (see the carry comment above)
        cache_final = carry[3] if not host else cache
        ckpt.save_serving_state(args.export_serve, args.steps, carry[0],
                                cache_final, cache_cfg=cache_cfg)
        print(f"exported serving state (params + warm cache) to "
              f"{args.export_serve}")
    jax.block_until_ready(carry[0])
    dt = time.perf_counter() - t0
    nodes_per_iter = batch.nodes_per_iteration()
    out = {"losses": losses, "nodes_per_iter": nodes_per_iter, "wall_s": dt,
           "capacity_slack": slack, "n_dropped": n_dropped,
           "setup_s": t0 - t_setup}
    if not host:
        # the last trained batch and what generated it, for checks that
        # regenerate it (host-mode batches hold staged holes instead)
        last = max(args.steps - 1, start)
        out.update(batch=carry[2], batch_seeds=seeds_for(last),
                   batch_rng=rngs[last], device_args=device_args,
                   mesh=mesh)
    if host:
        out["host_gather_mb"] = store.bytes_issued / 1e6
        print(f"L3 host gathers shipped {out['host_gather_mb']:.1f} MB "
              f"over PCIe")
    print(f"trained {args.steps - start} steps in {dt:.1f}s "
          f"({nodes_per_iter} padded nodes/iter, "
          f"{(args.steps - start) * nodes_per_iter / dt:,.0f} nodes/s)")
    if cached:
        out["cache_hit_rate"] = carry[2].cache_hit_rate()
        print(f"steady-state cache hit rate: {out['cache_hit_rate']:.3f}")
    return out


def train_lm(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    api = zoo.build(cfg)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       microbatches=args.microbatches)
    params = api.init(jax.random.PRNGKey(args.seed))
    state = init_state(params, tcfg)
    step = jax.jit(make_train_step(api.loss, tcfg))

    start = 0
    if args.resume and ckpt.latest_step(args.ckpt_dir) is not None:
        start = ckpt.latest_step(args.ckpt_dir)
        state = ckpt.restore(args.ckpt_dir, start, state)
        print(f"resumed from step {start}")

    rng = np.random.default_rng(args.seed)
    b, s = args.lm_batch, args.lm_seq
    losses = []
    t0 = time.perf_counter()
    for t in range(start, args.steps):
        toks = rng.integers(0, cfg.vocab_size, (b, s), dtype=np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "labels": jnp.asarray(np.roll(toks, -1, axis=1))}
        if cfg.family == "vlm":
            batch["vision"] = jnp.asarray(
                rng.standard_normal((b, cfg.n_vision_tokens, cfg.d_vision),
                                    dtype=np.float32))
        if cfg.family == "audio":
            batch["frames"] = jnp.asarray(
                rng.standard_normal((b, cfg.n_audio_frames, cfg.d_audio),
                                    dtype=np.float32))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        if (t + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, t + 1, state, keep=tcfg.keep_checkpoints)
        if (t + 1) % args.log_every == 0:
            print(f"step {t+1}: loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
    dt = time.perf_counter() - t0
    print(f"trained {args.steps - start} steps in {dt:.1f}s")
    return {"losses": losses, "wall_s": dt}


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (``main`` parses ``sys.argv`` with it;
    scripts that drive ``train_gcn`` parse their own argument lists)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="graphgen-gcn")
    ap.add_argument("--fanouts", default=None,
                    help="comma-separated per-hop fanouts override, e.g. 15,10,5")
    ap.add_argument("--capacity-slack", type=float, default=None,
                    help="feature-shuffle capacity slack; omit to auto-size "
                         "from a drop-aware calibration step")
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="hot-node feature cache rows/worker (rounded UP "
                         "to a power of two; 0 disables; default from "
                         "config)")
    ap.add_argument("--cache-admit", type=int, default=None,
                    help="misses before a node id is admitted to the cache")
    ap.add_argument("--cache-assoc", type=int, default=None,
                    choices=[1, 2, 4],
                    help="cache ways per set (1 = direct-mapped)")
    ap.add_argument("--cache-mode", default=None,
                    choices=["replicated", "sharded", "tiered"],
                    help="cache placement: per-worker replicas, id-space "
                         "shards with cache-aware routing, or a "
                         "replicated L1 head in front of the sharded L2")
    ap.add_argument("--l1-rows", type=int, default=None,
                    help="tiered mode: replicated L1 rows/worker (rounded "
                         "UP to a power of two; 0 auto-sizes to "
                         "cache_rows/8)")
    ap.add_argument("--l1-promote", type=int, default=None,
                    help="tiered mode: observations of a row before it is "
                         "promoted into the local L1")
    ap.add_argument("--probe-wire", default=None,
                    choices=["dense", "compact"],
                    help="shard-probe response wire format: dense ships "
                         "the full [W, cap, D] row block, compact (the "
                         "config default) ships a hit bitmap + a row "
                         "payload bounded by the calibrated hit cap")
    ap.add_argument("--probe-hit-cap", type=int, default=None,
                    help="compact wire: pin the probe-response payload "
                         "rows per destination (skips the hit-cap "
                         "calibration ladder; 0 = auto, half the probe "
                         "capacity)")
    ap.add_argument("--feature-store", default=None,
                    choices=["device", "host"],
                    help="where the feature table lives: device row-shards "
                         "it over the workers, host keeps it in host RAM "
                         "behind the async L3 gather tier (for tables "
                         "beyond aggregate device memory)")
    ap.add_argument("--host-gather-depth", type=int, default=None,
                    choices=[1, 2],
                    help="host store gather pipeline depth: 2 overlaps the "
                         "gather with the compute step (default), 1 "
                         "gathers synchronously (the overlap-off baseline)")
    ap.add_argument("--autotune", action="store_true",
                    help="replace the serial calibration ladders with one "
                         "instrumented trace window + an offline cost-model "
                         "search over (fanouts, cache_rows, l1_rows, assoc, "
                         "hit_cap, capacity_slack); a live validator "
                         "accepts the pick or falls back to the ladders")
    ap.add_argument("--autotune-steps", type=int, default=8,
                    help="instrumented steps the autotune trace records "
                         "(the cold half is excluded from the fit; fewer "
                         "than 4 degrades to the calibration ladders)")
    ap.add_argument("--warm-recalibrate", type=int, default=0,
                    help="after N warm steps, shrink the owner-exchange "
                         "capacity to the observed steady-state cache-miss "
                         "peak (0 disables; needs the cache and W > 1)")
    ap.add_argument("--cache-probe-impl", default="jnp",
                    choices=["jnp", "pallas"],
                    help="cache probe implementation: XLA gather+compare or "
                         "the fused Pallas VMEM kernel (native on TPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--avg-degree", type=float, default=10.0)
    ap.add_argument("--batch-per-worker", type=int, default=32)
    ap.add_argument("--lm-batch", type=int, default=4)
    ap.add_argument("--lm-seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--export-serve", default=None, metavar="DIR",
                    help="after training, checkpoint params + the warm "
                         "cache state for the serving tier "
                         "(repro.launch.serve --warm-from DIR)")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.cache_probe_impl != "jnp":
        from ..core.feature_cache import set_probe_impl
        set_probe_impl(args.cache_probe_impl)
    if get_config(args.arch).is_gnn:
        train_gcn(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
