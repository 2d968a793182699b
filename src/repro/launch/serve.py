"""Serving drivers: the graph-serving tier and the LM decode loop.

Graph serving (``--arch graphgen-gcn``) is the production half of
GraphGen+: a *frozen* model answering seed-node requests at low latency.
Requests flow through three stages:

1. **bounded request queue** — a producer thread enqueues seed-id
   batches; the server drains them (backpressure is the queue bound);
2. **bucket ladder** — each request's batch size is padded up to the
   smallest bucket in a small shape ladder, and the ladder is compiled
   once at startup, so a request NEVER lands on a re-JIT (the latency
   killer the JIT-compiled-inference paper names);
3. **read-mostly fetch** — subgraph generation + a forward-only GNN run
   against the tiered L1/L2 feature cache in its frozen serve view
   (``CacheConfig.serve_view()``): probes serve hits, the admit stage is
   the identity, and the warm state — restored from a training
   checkpoint (``--warm-from``, see ``train.checkpoint``) or built by a
   dedicated warmup sweep over the Zipf head — is bit-stable across
   requests.

LM serving (any zoo arch id) drives batched autoregressive decoding with
a KV/SSM cache, token-by-token.

Examples:
    PYTHONPATH=src python -m repro.launch.serve --arch graphgen-gcn \\
        --smoke --requests 64
    REPRO_FORCE_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \\
        --arch graphgen-gcn --smoke --workers 4 --buckets 8,16,32
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \\
        --batch 4 --prompt-len 16 --gen-len 16
"""
import os
if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['REPRO_FORCE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse        # noqa: E402
import queue           # noqa: E402
import threading       # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np     # noqa: E402

from ..configs import get_config, smoke_config          # noqa: E402
from ..core.feature_cache import CacheConfig            # noqa: E402
from ..core.generation import (make_distributed_generator,  # noqa: E402
                               make_generator_fn)
from ..core.partition import partition_edges            # noqa: E402
from ..graph.synthetic import (node_features, node_labels,  # noqa: E402
                               powerlaw_graph)
from ..models import zoo                                # noqa: E402
from ..train import checkpoint as ckpt                  # noqa: E402
from .compile_cache import enable_compile_cache        # noqa: E402
from .mesh import make_mesh                             # noqa: E402

#: default request-shape ladder: per-worker seed slots per bucket.  Small
#: on purpose — each bucket is one compiled program resident for the
#: server's lifetime, and pad waste is bounded by the ladder's spacing.
DEFAULT_BUCKETS = (8, 16, 32)


def jit_compile_count(jitted) -> int:
    """Compiled-program count of a ``jax.jit``-wrapped callable — the
    zero-recompile probe the serving tier and ``benchmarks/serve_latency``
    assert with.  Reads the jit executable-cache size: one entry per
    traced input signature, so a request that lands on an un-compiled
    shape is visible as a count increase."""
    size = getattr(jitted, "_cache_size", None)
    if size is None:
        raise RuntimeError(
            "this jax build exposes no jit cache-size probe "
            "(jit_fn._cache_size) — the zero-recompile gate cannot run")
    return int(size())


def bucket_for(n: int, buckets, n_workers: int) -> int:
    """Smallest ladder bucket (per-worker seed slots) whose padded
    capacity ``bucket * n_workers`` holds an ``n``-seed request.  Raises
    on a request larger than the ladder's top bucket — an oversized
    request must be split by the caller, never silently truncated."""
    if n <= 0:
        raise ValueError(f"a request needs at least one seed, got {n}")
    for b in buckets:
        if b * n_workers >= n:
            return b
    raise ValueError(
        f"request of {n} seeds exceeds the bucket ladder's capacity "
        f"{buckets[-1] * n_workers} (buckets {tuple(buckets)} x "
        f"{n_workers} workers) — split the request or widen the ladder")


def warmup_sweep(gen_fn, device_args, cache, head_ids, *, n_workers: int,
                 bucket: int, sweeps: int, seed: int = 0):
    """Pre-warm a cache state for serving: run the MUTABLE generator over
    the Zipf head before any request arrives.

    ``head_ids`` is the hot node-id population, hottest first (e.g. ids
    in descending degree order); each sweep feeds the next
    ``bucket * n_workers`` of them (wrapping) through
    ``gen_fn(device_args, seeds, rng, cache) -> (batch, cache)``, so the
    head rows — and the hot neighbors their fanouts pull in — pass the
    frequency-admission threshold and are resident before the serve view
    freezes the state.  Returns the warmed cache."""
    head = np.asarray(head_ids, np.int32).reshape(-1)
    if head.size == 0:
        raise ValueError("warmup_sweep needs a non-empty head population")
    per = bucket * n_workers
    rng0 = jax.random.PRNGKey(seed)
    for t in range(sweeps):
        take = (np.arange(per) + t * per) % head.size
        seeds = jnp.asarray(head[take].reshape(n_workers, bucket))
        _, cache = gen_fn(device_args, seeds, jax.random.fold_in(rng0, t),
                          cache)
    return cache


class GraphServer:
    """Read-mostly graph-serving engine: frozen params + warm cache +
    a compiled bucket ladder.

    Holds ONE warm cache state and one parameter tree, both read-only,
    and answers ``serve(seed_ids) -> class predictions`` by padding the
    request to its ladder bucket and running the forward-only program
    (frozen-cache subgraph generation + the GNN's ``logits`` + argmax)
    compiled for that bucket; ``logits(params, batch)`` is the family's,
    from ``zoo.build``.  Call :meth:`warmup` once at startup to compile
    every bucket; after that the request path never traces —
    :meth:`compile_count` is the probe that proves it."""

    def __init__(self, gen_fn, device_args, params, cache, *, logits,
                 buckets=DEFAULT_BUCKETS, n_workers: int, seed: int = 0):
        self._buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self._buckets or self._buckets[0] <= 0:
            raise ValueError(f"bucket ladder must name positive sizes, "
                             f"got {buckets}")
        self._w = int(n_workers)
        self._device_args = device_args
        self._params = params
        self._cache = cache
        self._rng0 = jax.random.PRNGKey(seed)
        self._n_requests = 0
        cached = cache is not None

        def _step(device_args, seeds, rng, cache, params):
            if cached:
                batch = gen_fn(device_args, seeds, rng, cache)
            else:
                batch = gen_fn(device_args, seeds, rng)
            return jnp.argmax(logits(params, batch),
                              axis=-1).astype(jnp.int32)

        self._step = jax.jit(_step)

    @property
    def buckets(self) -> tuple:
        """The ladder: per-worker seed slots per bucket, ascending."""
        return self._buckets

    @property
    def capacity(self) -> int:
        """Largest request (seed count) the ladder can hold."""
        return self._buckets[-1] * self._w

    def compile_count(self) -> int:
        """Programs compiled so far (one per traced bucket shape).  After
        :meth:`warmup` this equals ``len(buckets)`` and MUST NOT grow on
        the request path — the zero-recompile serving invariant."""
        return jit_compile_count(self._step)

    def warmup(self) -> int:
        """Compile the whole ladder by serving one synthetic request per
        bucket (startup cost, paid exactly once — never on the request
        path).  Returns the compiled-program count, the baseline the
        request loop's zero-recompile assertion compares against."""
        for b in self._buckets:
            self.serve(np.zeros(b * self._w, np.int32))
        return self.compile_count()

    def serve(self, seed_ids) -> np.ndarray:
        """Answer one request: ``int32`` class predictions, one per seed.

        The request is padded to its ladder bucket (repeating the last
        seed — any valid id; the padded slots' predictions are sliced
        off), spread row-major across the worker axis, and run through
        the bucket's already-compiled program.  Blocks until the
        predictions are on host — the caller's clock reads end-to-end
        request latency."""
        ids = np.asarray(seed_ids, np.int32).reshape(-1)
        n = ids.size
        b = bucket_for(n, self._buckets, self._w)
        padded = np.empty(b * self._w, np.int32)
        padded[:n] = ids
        padded[n:] = ids[n - 1]
        seeds = jnp.asarray(padded.reshape(self._w, b))
        rng = jax.random.fold_in(self._rng0, self._n_requests)
        self._n_requests += 1
        preds = self._step(self._device_args, seeds, rng, self._cache,
                           self._params)
        return np.asarray(preds)[:n]


def _zipf_request_stream(rng, n_requests, head_order, max_size):
    """Synthetic serve traffic: request sizes uniform in [1, max_size],
    seed ids Zipf-ranked over ``head_order`` (hot head requested most —
    the access pattern the warm cache exists for)."""
    n_nodes = head_order.size
    for _ in range(n_requests):
        size = int(rng.integers(1, max_size + 1))
        ranks = np.minimum(rng.zipf(1.5, size=size), n_nodes) - 1
        yield head_order[ranks]


def serve_gcn(args) -> dict:
    """Graph-serving driver: build the read-mostly server, then drain a
    bounded queue of synthetic seed-node requests through it.

    Setup mirrors the training driver (power-law graph, partitioning,
    feature/label tables), then: warm the cache (``--warm-from`` restores
    a training checkpoint's params + cache state; otherwise a
    ``--warmup-sweeps`` sweep over the degree-ranked Zipf head), compile
    the bucket ladder, and serve ``--requests`` requests from a
    ``--queue-depth``-bounded queue fed by a producer thread.  Reports
    p50/p99 end-to-end latency, sustained QPS, and the request-path
    compile count (which must be zero)."""
    w = args.workers
    mesh = make_mesh((w,), ("data",))
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cache_cfg = CacheConfig.from_model(cfg)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    graph = powerlaw_graph(args.nodes, avg_degree=args.avg_degree,
                           n_hot=max(args.nodes // 1000, 1), seed=args.seed)
    part = partition_edges(graph, w)
    feats = node_features(graph.n_nodes, cfg.gcn_in_dim, args.seed)
    labels = node_labels(graph.n_nodes, cfg.n_classes, args.seed)
    model = zoo.build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    # degree-ranked hot head: warmup population AND the synthetic request
    # stream's Zipf rank -> id mapping
    head_order = np.argsort(
        -np.diff(graph.indptr)).astype(np.int32)

    cache = None
    if cache_cfg is not None:
        gen_mut, device_args, cache0 = make_distributed_generator(
            mesh, part, feats, labels, fanouts=cfg.fanouts,
            cache_cfg=cache_cfg)
        serve_cfg = cache_cfg.serve_view()
        if args.warm_from:
            from jax.sharding import NamedSharding, PartitionSpec as P
            shardings = {
                "params": jax.tree.map(
                    lambda _: NamedSharding(mesh, P()), params),
                "cache": jax.tree.map(
                    lambda _: NamedSharding(mesh, P("data")), cache0),
            }
            params, cache = ckpt.restore_serving_state(
                args.warm_from, params, cache0, shardings=shardings,
                expect_cache_cfg=serve_cfg)
            print(f"restored serving state from {args.warm_from} "
                  f"(params + warm cache)")
        else:
            head = head_order[:max(buckets[-1] * w,
                                   args.warmup_head or cache_cfg.n_rows)]
            cache = warmup_sweep(gen_mut, device_args, cache0, head,
                                 n_workers=w, bucket=buckets[-1],
                                 sweeps=args.warmup_sweeps, seed=args.seed)
            print(f"warmup sweep: {args.warmup_sweeps} sweeps over the "
                  f"{head.size}-node Zipf head")
        # the serve generator: same mesh/placement, frozen serve view
        gen_serve = make_generator_fn(mesh, fanouts=cfg.fanouts,
                                      cache_cfg=serve_cfg)
    else:
        gen_serve, device_args = make_distributed_generator(
            mesh, part, feats, labels, fanouts=cfg.fanouts)

    server = GraphServer(gen_serve, device_args, params, cache,
                         logits=model.logits, buckets=buckets, n_workers=w,
                         seed=args.seed)
    server.warmup()
    startup_compiles = server.compile_count()
    print(f"bucket ladder {server.buckets} compiled at startup "
          f"({startup_compiles} programs, capacity "
          f"{server.capacity} seeds/request)")

    req_q = queue.Queue(maxsize=args.queue_depth)
    rng = np.random.default_rng(args.seed + 7)

    def _producer():
        # enqueue the synthetic request stream; the bounded queue is the
        # backpressure (put blocks while the server is `queue-depth`
        # requests behind).  None is the drain sentinel.
        for ids in _zipf_request_stream(rng, args.requests, head_order,
                                        server.capacity):
            req_q.put((time.perf_counter(), ids))
        req_q.put(None)

    latencies = []
    producer = threading.Thread(target=_producer, name="serve-producer")
    producer.start()
    try:
        t0 = time.perf_counter()
        while True:
            item = req_q.get()
            if item is None:
                break
            t_enq, ids = item
            server.serve(ids)
            latencies.append(time.perf_counter() - t_enq)
        wall = time.perf_counter() - t0
    finally:
        producer.join()

    request_compiles = server.compile_count() - startup_compiles
    p50, p99 = (np.percentile(latencies, [50, 99]) * 1e3
                if latencies else (0.0, 0.0))
    qps = len(latencies) / wall if wall > 0 else 0.0
    print(f"served {len(latencies)} requests in {wall:.2f}s "
          f"({qps:.1f} req/s): p50 {p50:.2f}ms p99 {p99:.2f}ms, "
          f"{request_compiles} request-path compiles")
    return {"p50_ms": float(p50), "p99_ms": float(p99), "qps": float(qps),
            "n_requests": len(latencies), "wall_s": float(wall),
            "request_path_compiles": int(request_compiles),
            "startup_compiles": int(startup_compiles)}


def serve_lm(args) -> dict:
    """LM serving driver: batched autoregressive decode with a KV/SSM
    cache, prefilling token-by-token through the decode path (exercises
    the cache; a production server would run the batched prefill
    forward), then timing ``--gen-len`` decode steps.

    With ``--prompt-len 0`` generation starts from a fixed BOS-like
    token (id 0) — there are no prompt logits to argmax.  The timed loop
    accumulates DEVICE arrays and transfers to host only after the final
    ``block_until_ready``, so the tok/s figure measures decode, not one
    forced host sync per token."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    api = zoo.build(cfg)
    if api.decode is None:
        raise SystemExit(f"{args.arch} has no decode path")
    params = api.init(jax.random.PRNGKey(args.seed))
    total = args.prompt_len + args.gen_len
    cache = api.init_cache(args.batch, total)
    decode = jax.jit(api.decode)

    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len),
                          dtype=np.int32)
    logits = None
    for p in range(args.prompt_len):
        logits, cache = decode(params, cache, jnp.asarray(prompt[:, p:p+1]),
                               jnp.int32(p))
    pos = args.prompt_len
    if logits is None:
        # zero-trip prefill: nothing to argmax — start from a fixed token
        tok = jnp.zeros((args.batch, 1), jnp.int32)
    else:
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out = []
    jax.block_until_ready(tok)          # the clock starts on settled inputs
    t0 = time.perf_counter()
    for _ in range(args.gen_len):
        out.append(tok)                 # device array — no host sync here
        logits, cache = decode(params, cache, tok, jnp.int32(pos))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        pos += 1
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    toks = args.gen_len * args.batch
    print(f"generated {toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s batched)")
    gen = (np.concatenate([np.asarray(t) for t in out], axis=1)
           if out else np.zeros((args.batch, 0), np.int32))
    if gen.size:
        print("sample token ids:", gen[0][:16])
    return {"tok_s": toks / dt, "tokens": gen}


def main() -> None:
    """CLI entry: dispatch on the arch family — GNN archs (``is_gnn``)
    get the graph-serving tier, zoo archs the LM decode driver."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # --- LM decode flags -------------------------------------------------
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    # --- graph-serving flags ---------------------------------------------
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=20_000)
    ap.add_argument("--avg-degree", type=float, default=10.0)
    ap.add_argument("--buckets", default="8,16,32",
                    help="request-shape ladder: per-worker seed slots, "
                         "comma-separated ascending (compiled at startup)")
    ap.add_argument("--requests", type=int, default=256,
                    help="synthetic requests to serve")
    ap.add_argument("--queue-depth", type=int, default=32,
                    help="bounded request-queue size (backpressure)")
    ap.add_argument("--warmup-sweeps", type=int, default=8,
                    help="mutable-generator sweeps over the Zipf head "
                         "before freezing the cache")
    ap.add_argument("--warmup-head", type=int, default=0,
                    help="head population size for the warmup sweep "
                         "(0 = the cache's row count)")
    ap.add_argument("--warm-from", default=None,
                    help="restore params + warm cache from a serving "
                         "checkpoint dir (train.py --export-serve) "
                         "instead of sweeping")
    args = ap.parse_args()
    enable_compile_cache()
    if get_config(args.arch).is_gnn:
        rec = serve_gcn(args)
        if rec["request_path_compiles"]:
            raise SystemExit(
                f"{rec['request_path_compiles']} requests compiled on the "
                f"request path: the bucket ladder does not cover the "
                f"request stream")
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
