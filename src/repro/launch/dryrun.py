"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k [--multi-pod] [--attn chunked] [--remat full] \
        [--variant name] [--out results.jsonl]

This proves the distribution config is coherent without hardware: the
sharded program must partition (no sharding mismatches), compile (no
unsupported collectives), and fit (memory_analysis).
"""
# The VERY FIRST lines, before ANY other import (jax locks the device count
# on first init):
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
import time         # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import REGISTRY, SUBQUADRATIC, get_config   # noqa: E402
from ..core.config import SHAPES, TrainConfig              # noqa: E402
from ..models import layers as L                           # noqa: E402
from ..models import zoo                                   # noqa: E402
from ..train.train_loop import init_state, make_train_step # noqa: E402
from .hlo_analysis import collective_bytes, trip_weighted_cost, xla_cost  # noqa: E402
from .mesh import make_production_mesh                     # noqa: E402


def _artifact_stats(compiled, chips: int, t_lower: float, t_compile: float) -> dict:
    cost = xla_cost(compiled)
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    out = dict(
        chips=chips,
        flops_per_device=trip_weighted_cost(hlo)["flops"],
        bytes_per_device=trip_weighted_cost(hlo)["bytes"],
        xla_cost_flops=float(cost.get("flops", 0.0)),
        xla_cost_bytes=float(cost.get("bytes accessed", 0.0)),
        collective_bytes_per_device=collective_bytes(hlo),
        t_lower_s=round(t_lower, 1),
        t_compile_s=round(t_compile, 1),
    )
    try:
        out["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
        }
    except Exception:
        out["memory"] = str(mem)
    return out


def lower_gcn_cell(rec: dict, arch: str, multi_pod: bool,
                   merge_mode: str = "butterfly",
                   cache_rows: int = None, cache_mode: str = None,
                   l1_rows: int = None, probe_wire: str = None,
                   feature_store: str = None,
                   collect_stats: bool = False) -> dict:
    """The paper's own workload at production scale: one synchronized
    generation+training step on a 530M-node / 5B-edge graph (the paper's
    evaluation graph).  The sampling depth comes from the arch config —
    2-hop (40, 20) for the paper cell, 1-hop for graphgen-sage, 3-hop for
    graphgen-gcn-deep (~1.7M padded nodes per iteration at (40, 20)),
    and the model (init and loss) from ``zoo.build``, at the arch's own
    widths.  Generation shards over 'data' (the worker axis); the small
    GNN replicates over 'model'.  When the config enables the hot-node
    feature cache, its per-worker state rides in the pipelined carry —
    ``(params, opt, batch, cache)`` — and must partition/compile too.

    With ``feature_store="host"`` the cell lowers the L3 path: the
    feature table never appears among the device args (it lives in host
    RAM), the carry grows the in-flight ``HostMissRequest``, and the
    step takes the landed ``[W, S, D]`` gather buffer — proving the
    issue/collect split partitions and compiles at production scale
    WITHOUT materializing a 530M-row device table spec.

    With ``collect_stats=True`` the cell lowers the autotuner's trace
    recorder instead: the instrumented generator alone (the recorder
    times it outside the train step), whose output grows the stacked
    per-worker ``(FetchStats, CacheStats)`` tail — proving the
    telemetry seam partitions and compiles at production scale too."""
    from ..core.feature_cache import CacheConfig, cache_state_specs
    from ..core.generation import make_generator_fn, probe_round_capacity
    from ..core.host_store import HostMissRequest
    from ..core.pipeline import make_host_consume_step, make_pipelined_step
    from ..graph.subgraph import batch_specs, slots_per_seed
    from ..models import zoo
    from ..train.optimizer import adam_update, init_adam

    mesh = make_production_mesh(multi_pod=multi_pod)
    axis = "data"
    w = mesh.shape[axis]
    cfg = get_config(arch)
    if cache_rows is not None:
        cfg = dataclasses.replace(cfg, cache_rows=cache_rows)
    if cache_mode is not None:
        cfg = dataclasses.replace(cfg, cache_mode=cache_mode)
    if l1_rows is not None:
        cfg = dataclasses.replace(cfg, cache_l1_rows=l1_rows)
    if probe_wire is not None:
        cfg = dataclasses.replace(cfg, cache_wire=probe_wire)
    if feature_store is not None:
        cfg = dataclasses.replace(cfg, feature_store=feature_store)
    host = cfg.feature_store == "host"
    cache_cfg = CacheConfig.from_model(cfg)
    cached = cache_cfg is not None
    fanouts = cfg.fanouts
    n_nodes = 530_000_000
    n_edges = 5_000_000_000
    b = 128                                  # seeds per worker
    rows = -(-n_nodes // w)
    e_pad = -(-n_edges // w)
    s = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    seeds = s((w, b), i32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    slack = cfg.capacity_slack if cfg.capacity_slack is not None else 2.0
    gen_fn = make_generator_fn(mesh, fanouts=fanouts, axis_name=axis,
                               merge_mode=merge_mode,
                               capacity_slack=slack,
                               cache_cfg=cache_cfg,
                               feature_store=cfg.feature_store,
                               feat_dim=cfg.gcn_in_dim if host else None,
                               collect_stats=collect_stats)
    tcfg = TrainConfig()

    model = zoo.build(cfg)

    def train_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt, _ = adam_update(tcfg, params, grads, opt)
        return params, opt, loss

    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: init_adam(params))
    batch0 = batch_specs(w * b, fanouts, cfg.gcn_in_dim, n_workers=w)
    if collect_stats:
        # trace-recorder cell: the instrumented generator alone (the
        # recorder drives it outside the train step, see
        # repro.launch.autotune.record_trace)
        if host:
            device_args = (s((w, n_nodes + 1), i32), s((w, e_pad), i32),
                           s((w * rows, 1), f32))
        else:
            device_args = (s((w, n_nodes + 1), i32), s((w, e_pad), i32),
                           s((w * rows, cfg.gcn_in_dim), f32),
                           s((w * rows, 1), f32))
        gen_args = [device_args, seeds, rng]
        if cached:
            gen_args.append(cache_state_specs(cache_cfg, cfg.gcn_in_dim,
                                              n_workers=w))
        if host and cached:
            r = b * slots_per_seed(fanouts)
            stage = max(int(probe_round_capacity(r, 1, slack)), 1)
            gen_args += [s((w, stage), i32),
                         s((w, stage, cfg.gcn_in_dim), f32)]
        t0 = time.time()
        lowered = jax.jit(gen_fn).lower(*gen_args)
        t_lower = time.time() - t0
    elif host:
        # the runtime loop dispatches gen and patch+train as SEPARATE
        # programs (the gather must ride between them — see
        # pipeline.pipelined_loop); for the cost view, lower one
        # iteration's worth of device work as a single composite
        consume = make_host_consume_step(train_fn)

        if cached:
            def step(carry, device_args, seeds, rng, landed):
                params, opt, batch, req, cache = carry
                nb, cache, nreq = gen_fn(device_args, seeds, rng, cache,
                                         req.ids, landed)
                params, opt, loss = consume(params, opt, batch, req, landed)
                return (params, opt, nb, nreq, cache), loss
        else:
            def step(carry, device_args, seeds, rng, landed):
                params, opt, batch, req = carry
                nb, nreq = gen_fn(device_args, seeds, rng)
                params, opt, loss = consume(params, opt, batch, req, landed)
                return (params, opt, nb, nreq), loss
        # no device feature table; per-worker staging size from the SAME
        # formula the compiled fetch uses (_host_fetch)
        device_args = (
            s((w, n_nodes + 1), i32),
            s((w, e_pad), i32),
            s((w * rows, 1), f32),
        )
        r = b * slots_per_seed(fanouts)
        stage = max(int(probe_round_capacity(r, 1, slack)), 1)
        req0 = HostMissRequest(ids=s((w, stage), i32),
                               slot=s((w, r), i32),
                               patch=s((w, r), jnp.bool_))
        landed = s((w, stage, cfg.gcn_in_dim), f32)
        if cached:
            cache0 = cache_state_specs(cache_cfg, cfg.gcn_in_dim,
                                       n_workers=w)
            carry0 = (params, opt, batch0, req0, cache0)
        else:
            carry0 = (params, opt, batch0, req0)
        t0 = time.time()
        lowered = jax.jit(step).lower(carry0, device_args, seeds, rng,
                                      landed)
        t_lower = time.time() - t0
    else:
        step = make_pipelined_step(gen_fn, train_fn, cached=cached)
        device_args = (
            s((w, n_nodes + 1), i32),
            s((w, e_pad), i32),
            s((w * rows, cfg.gcn_in_dim), f32),
            s((w * rows, 1), f32),
        )
        if cached:
            cache0 = cache_state_specs(cache_cfg, cfg.gcn_in_dim,
                                       n_workers=w)
            carry0 = (params, opt, batch0, cache0)
        else:
            carry0 = (params, opt, batch0)
        t0 = time.time()
        lowered = jax.jit(step).lower(carry0, device_args, seeds, rng)
        t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    rec.update(_artifact_stats(compiled, mesh.size, t_lower, time.time() - t0))
    rec.update(
        status="ok",
        params=cfg.param_count(),
        active_params=cfg.param_count(),
        cache_rows=cfg.cache_rows,
        cache_mode=cfg.cache_mode if cached else None,
        cache_l1_rows=cache_cfg.l1_rows if cached else 0,
        feature_store=cfg.feature_store,
        collect_stats=collect_stats,
        tokens=w * b * slots_per_seed(fanouts),   # padded node slots per iter
    )
    return rec


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               attn: str = "naive", remat: str = "keep",
               variant: str = "baseline", shard_heads: bool = False,
               gen_merge: str = "butterfly", moe_impl: str = "gather",
               seq_parallel: bool = False, compress: bool = False,
               cache_rows: int = None, cache_mode: str = None,
               l1_rows: int = None, probe_wire: str = None,
               feature_store: str = None,
               collect_stats: bool = False) -> dict:
    cfg = get_config(arch)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
    }
    if cfg.is_gnn:
        rec["kind"] = "train"
        return lower_gcn_cell(rec, arch, multi_pod, merge_mode=gen_merge,
                              cache_rows=cache_rows, cache_mode=cache_mode,
                              l1_rows=l1_rows, probe_wire=probe_wire,
                              feature_store=feature_store,
                              collect_stats=collect_stats)
    shape = SHAPES[shape_name]
    rec["kind"] = shape.kind
    if shape_name == "long_500k" and arch not in SUBQUADRATIC:
        rec["status"] = "skipped"
        rec["reason"] = ("quadratic-attention arch; long_500k runs on "
                         "SSM/hybrid only (DESIGN.md §4)")
        return rec
    if attn != "naive":
        L.set_attn_impl(attn)
    if shard_heads:
        L.set_shard_heads(True)
    if seq_parallel:
        L.set_seq_parallel(True)
    if moe_impl != "gather":
        from ..models import moe as moe_mod
        moe_mod.set_moe_impl(moe_impl)
    if remat != "keep":
        cfg = dataclasses.replace(cfg, remat=remat)

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    L.set_mesh(mesh)
    api = zoo.build(cfg)
    t0 = time.time()

    if shape.kind == "train":
        tcfg = TrainConfig(compress_grads=compress)
        params_shape = jax.eval_shape(api.init, jax.random.key(0))
        state_shape = jax.eval_shape(lambda p: init_state(p, tcfg), params_shape)
        pspecs = zoo.param_pspecs(cfg, params_shape, mesh)
        state_specs = type(state_shape)(
            params=pspecs,
            opt=type(state_shape.opt)(
                step=jax.sharding.PartitionSpec(), m=pspecs, v=pspecs
            ),
            error=pspecs if compress else None,
        )
        batch_shape = zoo.input_specs(cfg, shape)
        batch_specs = zoo.batch_pspecs(cfg, batch_shape, mesh)
        step = make_train_step(api.loss, tcfg, mesh)
        jitted = jax.jit(
            step,
            in_shardings=(zoo.to_shardings(mesh, state_specs),
                          zoo.to_shardings(mesh, batch_specs)),
            out_shardings=(zoo.to_shardings(mesh, state_specs), None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_shape, batch_shape)
    elif shape.kind == "prefill":
        params_shape = jax.eval_shape(api.init, jax.random.key(0))
        pspecs = zoo.param_pspecs(cfg, params_shape, mesh)
        batch_shape = zoo.prefill_specs(cfg, shape)
        batch_specs = zoo.batch_pspecs(cfg, batch_shape, mesh)
        fwd = lambda p, b: zoo.forward_logits(cfg, p, b)
        jitted = jax.jit(
            fwd,
            in_shardings=(zoo.to_shardings(mesh, pspecs),
                          zoo.to_shardings(mesh, batch_specs)),
        )
        lowered = jitted.lower(params_shape, batch_shape)
    else:  # decode
        params_shape = jax.eval_shape(api.init, jax.random.key(0))
        pspecs = zoo.param_pspecs(cfg, params_shape, mesh)
        cache_shape = jax.eval_shape(
            lambda: api.init_cache(shape.global_batch, shape.seq_len)
        )
        cache_specs = zoo.cache_pspecs(cfg, cache_shape, mesh)
        batch_shape = zoo.input_specs(cfg, shape)
        batch_specs = zoo.batch_pspecs(cfg, batch_shape, mesh)
        pos_shape = jax.ShapeDtypeStruct((), jnp.int32)

        def serve_step(params, cache, batch, pos):
            return api.decode(params, cache, batch["tokens"], pos)

        jitted = jax.jit(
            serve_step,
            in_shardings=(
                zoo.to_shardings(mesh, pspecs),
                zoo.to_shardings(mesh, cache_specs),
                zoo.to_shardings(mesh, batch_specs),
                None,
            ),
            out_shardings=(None, zoo.to_shardings(mesh, cache_specs)),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_shape, cache_shape, batch_shape, pos_shape)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    rec.update(_artifact_stats(compiled, chips, t_lower, time.time() - t0))
    rec.update(
        status="ok",
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        tokens=shape.global_batch
        * (shape.seq_len if shape.kind in ("train", "prefill") else 1),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--attn", default="naive", choices=["naive", "chunked"])
    ap.add_argument("--remat", default="keep",
                    choices=["keep", "none", "full", "dots"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--shard-heads", action="store_true")
    ap.add_argument("--gen-merge", default="butterfly",
                    choices=["butterfly", "reduce_scatter"])
    ap.add_argument("--moe", default="gather", choices=["gather", "ep_a2a"])
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    ap.add_argument("--cache-rows", type=int, default=None,
                    help="GCN cells: hot-node feature cache rows/worker "
                         "(0 disables; default from the arch config)")
    ap.add_argument("--cache-mode", default=None,
                    choices=["replicated", "sharded", "tiered"],
                    help="GCN cells: cache placement override")
    ap.add_argument("--l1-rows", type=int, default=None,
                    help="GCN cells, tiered mode: replicated L1 "
                         "rows/worker (0 auto-sizes to cache_rows/8)")
    ap.add_argument("--probe-wire", default=None,
                    choices=["dense", "compact"],
                    help="GCN cells: shard-probe response wire format "
                         "override (sharded/tiered modes)")
    ap.add_argument("--feature-store", default=None,
                    choices=["device", "host"],
                    help="GCN cells: feature-table placement override — "
                         "host lowers the L3 issue/collect path with NO "
                         "device feature table in the arg specs")
    ap.add_argument("--collect-stats", action="store_true",
                    help="GCN cells: lower the autotuner's instrumented "
                         "trace-recorder generator (the stacked "
                         "FetchStats/CacheStats telemetry tail) instead "
                         "of the train step")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()
    rec = lower_cell(args.arch, args.shape, args.multi_pod,
                     attn=args.attn, remat=args.remat, variant=args.variant,
                     shard_heads=args.shard_heads, gen_merge=args.gen_merge,
                     moe_impl=args.moe, seq_parallel=args.seq_parallel,
                     compress=args.compress, cache_rows=args.cache_rows,
                     cache_mode=args.cache_mode, l1_rows=args.l1_rows,
                     probe_wire=args.probe_wire,
                     feature_store=args.feature_store,
                     collect_stats=args.collect_stats)
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    if rec.get("status") not in ("ok", "skipped"):
        sys.exit(1)


if __name__ == "__main__":
    main()
