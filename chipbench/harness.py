"""One run of one cell: set-up, the measured window, the traced window,
and the checks that decide ``correct``.

Everything that belongs to a configuration, a cell, a model family or
a per-layer metric is data found by name under the benchmark's
directory: ``configs/<name>.json``, ``workloads/<name>.json``,
``graphs/<name>.py``, ``models/<family>.py`` (the family a
configuration's ``model`` names: its reference equations, parameters and
operation count) and ``metrics/<name>.py``.  The window drives the
program's own training path as ``repro.launch.train.train_gcn`` composes
it (``partition_edges``, ``balance_table``, ``make_distributed_generator``,
``make_pipelined_step`` over ``value_and_grad`` of the loss that
``repro.models.zoo.build`` gives the family, and ``adam_update``); the
loop around it is the benchmark's and mirrors ``train_gcn``'s steady
state.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
import types
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
#: host annotation that bounds the traced window
TRACE_WINDOW = "chipbench_window"
#: where a traced run writes its profile (removed once it is read)
TRACE_DIR = HERE / ".traces"
#: steps under the profiler after the measured window of a traced run
TRACE_STEPS = 6


def log(msg: str) -> None:
    """A progress line on standard error."""
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def read_json(root: Path, kind: str, name: str) -> dict:
    """``<root>/<kind>/<name>.json``."""
    path = Path(root) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = HERE):
    """``(workload, config)`` of the cell ``name``."""
    wl = read_json(root, "workloads", name)
    return wl, read_json(root, "configs", wl["config"])


def _load_module(root: Path, kind: str, name: str, what: str):
    """The module ``<root>/<kind>/<name>.py``."""
    path = Path(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {what} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = HERE):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _load_module(root, "metrics", name, "reader for metric").read


def family_module(name: str, root: Path = HERE):
    """The module ``models/<name>.py`` of a model family: ``init(key,
    model, depth)``, ``to_program(flat, model, depth)``,
    ``from_program(params)``, ``forward(flat, x_seed, x_hops, masks,
    dtype)``, ``flops_per_seed(fanouts, model)`` and ``BLOCK``."""
    return _load_module(root, "models", name, "model family")


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench.get(kind, [])
            if workload in m.get("workloads", [workload])]


def device_peaks(kind: str, root: Path = HERE) -> dict:
    """The peaks of a ``device_kind`` from ``peaks.json``; a device that
    is not in the table is an error."""
    table = json.loads((Path(root) / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileClock:
    """Seconds JAX spends compiling or loading programs (a persistent
    cache hit counts only its load) and how many it compiled or loaded,
    from ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def close(self) -> None:
        import jax
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def sub_seeds(seed: int, n: int) -> list:
    """``n`` 31-bit seeds drawn from ``--seed`` (any size of integer)."""
    st = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in st]


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of a configuration file."""
    from repro.core.config import ModelConfig
    m = dict(cfg["model"])
    m["fanouts"] = tuple(m["fanouts"])
    return ModelConfig(name=cfg["name"], **m)


def train_config(cfg: dict):
    """The program's ``TrainConfig`` of a configuration file."""
    from repro.core.config import TrainConfig
    return TrainConfig(**cfg["train"])


@dataclasses.dataclass
class Setup:
    """The program built once for a cell: graph placed, step compiled."""
    wl: dict
    cfg: dict
    dataset: object
    family: types.ModuleType
    mesh: object
    gen_fn: object
    device_args: tuple
    cache_cfg: object
    step: object
    tcfg: object
    seed_nodes: np.ndarray

    @property
    def workers(self) -> int:
        return int(self.wl["workers"])

    @property
    def batch(self) -> int:
        return int(self.wl["seeds_per_worker"])

    @property
    def fanouts(self) -> tuple:
        return tuple(self.cfg["model"]["fanouts"])


def build(wl: dict, cfg: dict, root: Path = HERE,
          data_dir: Optional[Path] = None) -> Setup:
    """Load the dataset, partition and place it, and build the jitted
    pipelined step, as ``train_gcn`` does."""
    import jax
    from repro.core.feature_cache import CacheConfig
    from repro.core.generation import make_distributed_generator
    from repro.core.partition import partition_edges
    from repro.core.pipeline import make_pipelined_step
    from repro.graph.csr import CSRGraph
    from repro.launch.mesh import make_mesh

    from chipbench import datasets

    w = int(wl["workers"])
    mesh = make_mesh((w,), ("data",))
    mcfg = model_config(cfg)
    family = family_module(mcfg.family, root)
    t = time.perf_counter()
    ds = datasets.load(cfg["dataset"], root, data_dir or datasets.DATA_DIR)
    log(f"dataset {ds.n_nodes} nodes, {ds.n_edges} edges in "
        f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    part = partition_edges(CSRGraph(ds.indptr, ds.indices), w)
    log(f"partition_edges in {time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    cache_cfg = CacheConfig.from_model(mcfg)
    gen_out = make_distributed_generator(
        mesh, part, ds.features, ds.labels, fanouts=mcfg.fanouts,
        capacity_slack=mcfg.capacity_slack, cache_cfg=cache_cfg)
    gen_fn, device_args = gen_out[0], gen_out[1]
    jax.block_until_ready(device_args)
    log(f"placement in {time.perf_counter() - t:.1f} s")
    tcfg = train_config(cfg)
    step = jax.jit(make_pipelined_step(gen_fn, _train_fn(tcfg, mcfg),
                                       cached=cache_cfg is not None))
    seed_nodes = np.flatnonzero(ds.degrees() > 0).astype(np.int32)
    return Setup(wl, cfg, ds, family, mesh, gen_fn, device_args, cache_cfg,
                 step, tcfg, seed_nodes)


def _train_fn(tcfg, mcfg):
    """``train_gcn``'s step 4: loss and gradient of the loss that
    ``zoo.build`` gives the family, then ``adam_update`` (looked up at
    trace time)."""
    import jax
    from repro.models import zoo
    from repro.train import optimizer
    loss_fn = zoo.build(mcfg).loss

    def train_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt, _ = optimizer.adam_update(tcfg, params, grads, opt)
        return params, opt, loss
    return train_fn


def _host_tree(batch) -> dict:
    return {"seeds": np.asarray(batch.seeds),
            "hops": [np.asarray(h) for h in batch.hops],
            "masks": [np.asarray(m) for m in batch.masks]}


class Run:
    """One seed's training run on a built program: priming, the first
    steps the reference follows, warm-up, and the measured loop."""

    #: steps the reference follows
    RECORDED = 3

    def __init__(self, s: Setup, seed: int):
        import jax
        from repro.core.balance import balance_table
        from repro.core.feature_cache import init_cache_state
        from repro.train.optimizer import init_adam
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.s = s
        k_params, k_rng = sub_seeds(seed, 2)
        depth = len(s.fanouts)
        m = s.cfg["model"]
        flat = jax.jit(lambda key: s.family.init(key, m, depth))(
            jax.random.PRNGKey(k_params))
        self.params0 = {k: np.asarray(v) for k, v in flat.items()}
        params = s.family.to_program(flat, m, depth)
        opt = init_adam(params)
        self.table = balance_table(s.seed_nodes, s.workers, seed)
        self.base_key = jax.random.PRNGKey(k_rng)
        self.t = 0
        carry_tail = ()
        if s.cache_cfg is not None:
            cache = jax.device_put(
                init_cache_state(s.cache_cfg, s.dataset.features.shape[1],
                                 s.workers),
                NamedSharding(s.mesh, P("data")))
            batch, cache = s.gen_fn(s.device_args, self.seeds_for(0),
                                    self.key_for(0), cache)
            carry_tail = (cache,)
        else:
            batch = s.gen_fn(s.device_args, self.seeds_for(0),
                             self.key_for(0))
        self.carry = (params, opt, batch) + carry_tail
        self.failed = 0
        self.dropped = int(np.asarray(batch.n_dropped).sum())
        self.total_dropped = self.dropped
        self.batches = [_host_tree(batch)]
        self.losses = []
        self.grad1 = None
        self.p3 = None
        self.hits = self.misses = 0

    def seeds_for(self, t: int):
        """``train_gcn``'s seeds of batch ``t``: columns ``t*b ..`` of the
        balance table, wrapping."""
        import jax.numpy as jnp
        sw = self.table.per_worker
        cols = (np.arange(self.s.batch) + t * self.s.batch) % sw.shape[1]
        return jnp.asarray(sw[:, cols])

    def key_for(self, t: int):
        """The sampling key of batch ``t``."""
        import jax
        return jax.random.fold_in(self.base_key, t)

    def step(self):
        """Dispatch one pipelined step (train batch t, generate batch
        t+1), then read back its loss and the new batch's counters."""
        import jax
        from jax.profiler import TraceAnnotation
        t = self.t + 1
        with TraceAnnotation("feed"):
            seeds = self.seeds_for(t)
            key = self.key_for(t)
        with TraceAnnotation("dispatch"):
            self.carry, loss = self.s.step(self.carry, self.s.device_args,
                                           seeds, key)
        nb = self.carry[2]
        with TraceAnnotation("readback"):
            loss, dropped, hits, misses = jax.device_get(
                (loss, nb.n_dropped, nb.n_cache_hits, nb.n_cache_misses))
        self.t = t
        dropped = int(np.sum(dropped))
        # self.dropped is what the batch trained here lost when generated
        if not math.isfinite(float(loss)) or self.dropped:
            self.failed += 1
        self.dropped = dropped
        self.total_dropped += dropped
        self.hits += int(np.sum(hits))
        self.misses += int(np.sum(misses))
        return float(loss)

    def record(self):
        """The first steps, kept for the reference: each trained batch's
        ids and masks, the losses, the first clipped gradient (Adam's
        first moment after step 1 over ``1 - beta1``) and the parameters
        after the last of them."""
        for i in range(self.RECORDED):
            self.losses.append(self.step())
            if i == 0:
                m = self.s.family.from_program(self.carry[1].m)
                self.grad1 = {k: v / (1.0 - self.s.tcfg.beta1)
                              for k, v in m.items()}
            if i + 1 < self.RECORDED:
                self.batches.append(_host_tree(self.carry[2]))
        self.p3 = self.s.family.from_program(self.carry[0])

    def final_batch(self):
        """The batch the last step generated, moved whole to the first
        device (where the checks run)."""
        import jax
        nb = self.carry[2]
        leaves = (nb.seeds, nb.hops, nb.masks, nb.x_seed, nb.x_hops,
                  nb.labels)
        return jax.device_put(leaves, jax.devices()[0])


def memory_peak(devices) -> int:
    """Largest ``peak_bytes_in_use`` over ``devices`` (0 where the
    backend keeps no such count)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def check_run(run: Run, final) -> dict:
    """Every number that decides ``correct``: the exact checks of the
    sampled ids and masks (the recorded batches and the window's last),
    of the last batch's rows and labels, and the model's three gaps
    against the reference."""
    from chipbench import checks, datasets, reference
    ds = run.s.dataset
    out = {"failed_steps": run.failed, "dropped": run.total_dropped}
    seeds, hops, masks, x_seed, x_hops, y = final
    last = {"seeds": np.asarray(seeds), "hops": [np.asarray(h) for h in hops],
            "masks": [np.asarray(m) for m in masks]}
    bad_ids = bad_masks = 0
    for b in run.batches + [last]:
        c = checks.check_sample(ds.indptr, ds.indices, b["seeds"],
                                b["hops"], b["masks"])
        bad_ids += c["bad_ids"]
        bad_masks += c["bad_masks"]
    out.update(bad_ids=bad_ids, bad_masks=bad_masks)
    table, labels = datasets.device_tables(run.s.cfg["dataset"], ds.n_nodes)
    out.update(checks.check_rows(table, labels, seeds, hops, masks, x_seed,
                                 x_hops, y))
    del final, x_seed, x_hops
    losses, grad1, p3 = reference.run_steps(
        run.s.cfg["train"], run.params0, table, labels, run.batches,
        forward=run.s.family.forward, block=run.s.family.BLOCK)
    out.update(checks.model_gaps(run.losses, run.grad1, run.params0, run.p3,
                                 losses, grad1, p3))
    return out


def trace_groups():
    """Predicates that split the step program's device operations."""
    return {
        "gen": lambda op: "jit(gen_fn)" in op.scope,
        "model": lambda op: "jit(step)" in op.scope
        and "jit(gen_fn)" not in op.scope,
        "collective": lambda op: op.is_collective(),
    }


def op_label(op) -> str:
    """An operation's HLO name and the tail of its JAX name stack."""
    return f"{op.name} {op.scope[-96:]}".strip()


def traced_window(run: Run, n_steps: int, step_hlo: str):
    """``n_steps`` more steps under the profiler; returns the trace's
    summary and the steps' mean distinct ids per worker."""
    import jax
    from chipbench import trace
    from jax.profiler import TraceAnnotation
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    h0, m0 = run.hits, run.misses
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host annotations only
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with TraceAnnotation(TRACE_WINDOW):
            for _ in range(n_steps):
                run.step()
    finally:
        jax.profiler.stop_trace()
    try:
        ops, spans = trace.load(trace.find_xplane(str(TRACE_DIR)),
                                {"jit_step": trace.hlo_op_names(step_hlo)})
        summary = trace.summarize(ops, spans, TRACE_WINDOW, trace_groups(),
                                  label=op_label)
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    distinct = ((run.hits - h0) + (run.misses - m0)) / (n_steps
                                                        * run.s.workers)
    return summary, distinct


def run_cell(name: str, seed: int, seconds: float, trace_on: bool, *,
             t_start: float, root: Path = HERE, bench: Optional[dict] = None,
             platform: str = "tpu", data_dir: Optional[Path] = None):
    """Run cell ``name`` once; returns ``(result dict, checks dict)``.
    ``platform`` is the one the devices must have (the command asks for
    ``tpu``); ``bench`` defaults to the checkout's ``BENCHMARK.json``."""
    import jax
    from repro.graph.subgraph import slots_per_seed
    from repro.launch.compile_cache import enable_compile_cache

    wl, cfg = load_cell(name, root)
    if bench is None:
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
    devices = jax.devices()
    if devices[0].platform != platform:
        raise SystemExit(f"chipbench: JAX finds no {platform.upper()} "
                         f"(platform {devices[0].platform!r})")
    if len(devices) < int(wl["chips"]):
        raise SystemExit(f"chipbench: cell {name} needs {wl['chips']} "
                         f"chips, JAX finds {len(devices)}")
    peaks = device_peaks(devices[0].device_kind, root)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    try:
        s = build(wl, cfg, root, data_dir)
        t = time.perf_counter()
        run = Run(s, seed)
        run.record()
        log(f"priming and {Run.RECORDED} recorded steps in "
            f"{time.perf_counter() - t:.1f} s (compile {clock.seconds:.1f} "
            f"s, {clock.cache_hits} persistent-cache hits)")
        for _ in range(int(wl["warmup_steps"]) - Run.RECORDED):
            run.step()
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.1f} s")
        compile_s = clock.seconds
        n0 = clock.count
        steps0, h0, m0, failed0 = run.t, run.hits, run.misses, run.failed
        t0 = time.perf_counter()
        while True:
            run.step()
            window_s = time.perf_counter() - t0
            if window_s >= seconds:
                break
        steps = run.t - steps0
        window_compiles = clock.count - n0
        counters = {"hits": run.hits - h0, "misses": run.misses - m0,
                    "slots": steps * s.workers * s.batch
                    * slots_per_seed(s.fanouts)}
        failed = run.failed - failed0
        summary = distinct = None
        if trace_on:
            hlo = s.step.lower(run.carry, s.device_args, run.seeds_for(0),
                               run.key_for(0)).compile().as_text()
            summary, distinct = traced_window(run, TRACE_STEPS, hlo)
    finally:
        clock.close()
    used = devices[:s.workers]
    peak = memory_peak(used)
    final = run.final_batch()
    run.carry = None
    s.device_args = s.step = s.gen_fn = None
    gc.collect()
    t = time.perf_counter()
    numbers = check_run(run, final)
    log(f"window {steps} steps in {window_s:.2f} s; checks in "
        f"{time.perf_counter() - t:.1f} s")
    limits = wl["limits"]
    from chipbench import checks
    correct = checks.verdict(numbers, limits)

    seeds_per_step = s.workers * s.batch
    measured = {"seeds_per_s": steps * seeds_per_step / window_s,
                "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
    metrics = {}
    if not trace_on:
        for m in cell_metrics(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = types.SimpleNamespace(
            wl=wl, cfg=cfg, family=s.family, workers=s.workers,
            seeds_per_worker=s.batch, fanouts=s.fanouts, compile_s=compile_s,
            window_compiles=window_compiles, window_s=window_s,
            window_steps=steps, counters=counters, trace=summary,
            traced_steps=TRACE_STEPS, traced_distinct=distinct,
            peaks=peaks)
        for m in cell_metrics(bench, name, "per_layer"):
            v = metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": steps,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    return result


def main(argv=None, *, t_start: Optional[float] = None, **test_only) -> int:
    """The command: run one cell once and print its result as the last
    line of standard output, and each checked number beside its limit as
    the last lines of standard error."""
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(REPO / "src"))
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start, **test_only)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result))
    return 0
