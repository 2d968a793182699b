"""The W=1 cached fetch as it was before it resolved sources first: a
verbatim copy of the former ``generation.fetch_rows`` body on that path
(a probe returning R x D rows, the owner fetch's R x D table gather, a
select into a row per distinct id, and ``out_u[inverse]``), of the cache
tiers' (probe, admit) strategies it dispatched to, and of the former
``_worker_generate``, whose row assembly sliced, reshaped and multiplied
that buffer by the masks.  ``make_former_generator_fn`` builds the
generator with them; ``tests/test_generation.py`` holds the current
generator and ``fetch_rows`` to it, batch, state and counters, step by
step.  Every other fetch (W > 1, the host store, no cache, the labels)
goes to the library's ``fetch_rows``."""
from typing import NamedTuple, Optional, Tuple
from unittest import mock

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import generation
from repro.core.feature_cache import (CacheConfig, CacheStats, FeatureCache,
                                      TieredCache,
                                      cache_insert, cache_probe, shard_of,
                                      tiered_probe)
from repro.core.generation import (Candidates, FetchStats, _WireStats,
                                   _routed_fetch, _shard_admit, _shard_probe,
                                   _stage, _staged, dedup_requests,
                                   local_candidates, merge_topk,
                                   probe_round_capacity)
from repro.core.tree_reduce import tree_allreduce, tree_reduce_scatter
from repro.graph.subgraph import SubgraphBatch


class _TierProbe(NamedTuple):
    """What a cache-mode strategy's probe stage hands back to ``fetch_rows``.

    ``l1_hit``/``local``/(``hit`` minus both) are the disjoint hit
    populations ``CacheStats`` reports; ``wire`` is the probe round's
    ``_WireStats`` telemetry (zeros / 0 bytes when no probe round ran);
    ``ctx`` is mode-private state the matching admit stage consumes
    (e.g. the shard-probe ``_RoutePlan``)."""
    hit: jax.Array       # [R] served by ANY cache tier
    rows: jax.Array      # [R, D] the serving tier's row copies
    l1_hit: jax.Array    # [R] subset served by the replicated L1 (tiered)
    local: jax.Array     # [R] subset served by THIS worker's main tier
    wire: _WireStats     # probe-round wire telemetry (see _WireStats)
    ctx: tuple           # opaque probe context for the admit stage


def _zeros_like_hits(ids):
    return jnp.zeros(ids.shape, jnp.bool_)


def _no_wire() -> _WireStats:
    """Wire telemetry of a fetch with no probe round (local probes only)."""
    return _WireStats(jnp.int32(0), jnp.int32(0), 0)


class _ReplicatedTier:
    """mode="replicated": local probe, local admission."""

    @staticmethod
    @_staged("cache_probe")
    def probe(cache, cfg, ids, valid, axis_name, cap, w):
        hit, rows = cache_probe(cache, ids, valid, cfg=cfg)
        return _TierProbe(hit, rows, _zeros_like_hits(ids), hit,
                          _no_wire(), ())

    @staticmethod
    @_staged("cache_insert")
    def admit(cache, cfg, probe, ids, fetched, should, axis_name, w):
        return cache_insert(cache, ids, fetched, should, cfg)


class _ShardedTier:
    """mode="sharded": one probe round to the shard holders, admission
    routed back on the same plan.  W == 1 degenerates to the replicated
    behavior (the single worker owns every shard)."""

    @staticmethod
    @_staged("cache_probe")
    def probe(cache, cfg, ids, valid, axis_name, cap, w):
        if w == 1:
            hit, rows = cache_probe(cache, ids, valid, cfg=cfg)
            return _TierProbe(hit, rows, _zeros_like_hits(ids), hit,
                              _no_wire(), ())
        hit, rows, plan, recv, wire = _shard_probe(cache, cfg, ids, valid,
                                                   axis_name, cap, w)
        local = jnp.logical_and(hit,
                                shard_of(ids, w) == lax.axis_index(axis_name))
        return _TierProbe(hit, rows, _zeros_like_hits(ids), local, wire,
                          (plan, recv))

    @staticmethod
    @_staged("cache_insert")
    def admit(cache, cfg, probe, ids, fetched, should, axis_name, w):
        if w == 1:
            return cache_insert(cache, ids, fetched, should, cfg)
        plan, recv = probe.ctx
        return _shard_admit(cache, cfg, plan, recv, fetched, should,
                            axis_name, w)


class _TieredTier:
    """mode="tiered": the three-stage composition — local L1 probe, shard
    probe (L2) for the L1 misses, owner fetch for the rest; admission
    updates the authoritative L2 shard AND offers the L2-served rows to
    the requester's L1 (installed after ``l1_promote`` observations)."""

    @staticmethod
    @_staged("cache_probe")
    def probe(cache, cfg, ids, valid, axis_name, cap, w):
        if w == 1:
            # single worker owns both tiers: the fused local probe (the
            # two-tier Pallas kernel when set_probe_impl('pallas'))
            l1_hit, l2_hit, rows = tiered_probe(cache, ids, valid, cfg=cfg)
            return _TierProbe(jnp.logical_or(l1_hit, l2_hit), rows,
                              l1_hit, l2_hit, _no_wire(),
                              (None, None, l2_hit))
        l1_hit, l1_rows = cache_probe(cache.l1, ids, valid,
                                      cfg=cfg.l1_config())
        # only L1 misses enter the probe round — the wire-byte win the
        # compact codec compounds (fewer probe hits -> a tighter hit_cap)
        l2_valid = jnp.logical_and(valid, ~l1_hit)
        l2_hit, l2_rows, plan, recv, wire = _shard_probe(
            cache.l2, cfg.l2_config(), ids, l2_valid, axis_name, cap, w)
        rows = jnp.where(l1_hit[:, None], l1_rows, l2_rows)
        local = jnp.logical_and(
            l2_hit, shard_of(ids, w) == lax.axis_index(axis_name))
        return _TierProbe(jnp.logical_or(l1_hit, l2_hit), rows, l1_hit,
                          local, wire, (plan, recv, l2_hit))

    @staticmethod
    @_staged("cache_insert")
    def admit(cache, cfg, probe, ids, fetched, should, axis_name, w):
        plan, recv, l2_hit = probe.ctx
        if w == 1:
            new_l2, n_l2 = cache_insert(cache.l2, ids, fetched, should,
                                        cfg.l2_config())
        else:
            new_l2, n_l2 = _shard_admit(cache.l2, cfg.l2_config(), plan,
                                        recv, fetched, should, axis_name, w)
        # L1 promotion is strictly L2 -> L1: only rows the L2 tier SERVED
        # this round (verbatim table copies that already survived the L2's
        # frequency admission — the proven-hot population) are offered to
        # the local L1, installing after l1_promote observations.  Owner-
        # fetched rows are deliberately NOT offered: they missed both
        # tiers, so letting them compete would churn the small L1's
        # admission tags with exactly the cold tail the threshold exists
        # to keep out.
        new_l1, n_l1 = cache_insert(cache.l1, ids, probe.rows, l2_hit,
                                    cfg.l1_config())
        return TieredCache(l1=new_l1, l2=new_l2), n_l2 + n_l1


#: mode -> (probe, admit) strategy — the SINGLE dispatch point; components
#: downstream of it (stats, routing, admission plumbing) are mode-agnostic
_CACHE_TIERS = {
    "replicated": _ReplicatedTier,
    "sharded": _ShardedTier,
    "tiered": _TieredTier,
}


class _FrozenTier:
    """Read-mostly serve view of a base strategy (``cfg.frozen``): the
    probe stage delegates verbatim — hits are served from the warm state
    through the base mode's full flow, probe round included — but the
    admit stage is the IDENTITY.  No admission, no L1 promotion, no
    tag/counter churn, and the admission ``all_to_all`` round disappears
    from the compiled program entirely, so a pre-warmed cache state is
    bit-stable across requests (the serving tier's correctness contract)
    and the request path pays only the probe collectives."""

    def __init__(self, base):
        self._base = base

    def probe(self, cache, cfg, ids, valid, axis_name, cap, w):
        """Delegate to the base mode's probe stage unchanged."""
        return self._base.probe(cache, cfg, ids, valid, axis_name, cap, w)

    def admit(self, cache, cfg, probe, ids, fetched, should, axis_name, w):
        """Identity: the cache state passes through untouched."""
        return cache, jnp.int32(0)


def _cache_tier(cfg: CacheConfig):
    """The (probe, admit) strategy pair for *cfg* — the base mode's pair,
    wrapped read-mostly when ``cfg.frozen`` selects the serve view."""
    if cfg.mode not in _CACHE_TIERS:
        raise ValueError(f"unknown cache mode {cfg.mode!r}; "
                         f"expected one of {sorted(_CACHE_TIERS)}")
    base = _CACHE_TIERS[cfg.mode]
    return _FrozenTier(base) if cfg.frozen else base


def fetch_rows(
    table_local: jax.Array,
    ids: jax.Array,
    axis_name: str,
    capacity_slack: float = 2.0,
    dedup: bool = True,
    capacity: Optional[int] = None,
    return_stats: bool = False,
    cache=None,
    cache_cfg: Optional[CacheConfig] = None,
    store: Optional[str] = None,
    feat_dim: Optional[int] = None,
    host_admit=None,
):
    """The former ``fetch_rows`` on the W=1 cached device-store path;
    every other call is the library's."""
    w = lax.axis_size(axis_name)
    r = ids.shape[0]
    if (cache is None or store == "host" or w != 1 or r == 0
            or (cache_cfg is not None and cache_cfg.store == "host")):
        return generation.fetch_rows(
            table_local, ids, axis_name, capacity_slack=capacity_slack,
            dedup=dedup, capacity=capacity, return_stats=return_stats,
            cache=cache, cache_cfg=cache_cfg, store=store,
            feat_dim=feat_dim, host_admit=host_admit)
    rows = table_local.shape[0]
    # the probe round carries ALL distinct ids, so it is sized from the
    # request count even when an explicit miss-sized `capacity` shrinks
    # the owner exchange (see docstring)
    slack_cap = probe_round_capacity(r, w, capacity_slack)
    cap = capacity
    if cap is None:
        cap = slack_cap
        if dedup:
            cap = min(cap, rows)    # ≤ rows distinct ids per destination
    if dedup:
        req_ids, inverse, req_valid, n_unique = dedup_requests(ids)
    else:
        req_ids, inverse = ids, None
        req_valid = jnp.ones((r,), jnp.bool_)
        n_unique = jnp.int32(r)
    # --- cache probe: hits never reach the owner fetch -------------------
    # the mode's (probe, admit) strategy pair is the only mode dispatch —
    # routing, admission plumbing, and stats below are mode-agnostic
    tier = None
    if cache is not None:
        tier = _cache_tier(cache_cfg)
    probe = None
    if tier is not None:
        probe = tier.probe(cache, cache_cfg, req_ids, req_valid,
                           axis_name, slack_cap, w)
    # --- route the (remaining) requests to their owners ------------------
    with _stage("owner_fetch"):
        route_valid = (req_valid if probe is None
                       else jnp.logical_and(req_valid, ~probe.hit))
        if w == 1:
            fetched = table_local[jnp.clip(req_ids, 0, rows - 1)]
            fetched = jnp.where(route_valid[:, None], fetched, 0)
            served_r = route_valid
        else:
            fetched, served_r = _routed_fetch(
                table_local, req_ids, route_valid, axis_name, cap, w, rows)
        n_routed = jnp.sum(route_valid).astype(jnp.int32)
    # --- merge hits back, offer served misses for admission --------------
    new_cache = None
    cstats = None
    if tier is not None:
        with _stage("slot_scatter"):
            out_u = jnp.where(probe.hit[:, None], probe.rows, fetched)
            served_u = jnp.logical_or(probe.hit, served_r)
            should = jnp.logical_and(route_valid, served_r)
        new_cache, n_ins = tier.admit(cache, cache_cfg, probe, req_ids,
                                      fetched, should, axis_name, w)
        row_bytes = table_local.shape[1] * jnp.dtype(table_local.dtype).itemsize
        with _stage("cache_probe"):
            n_hits = jnp.sum(probe.hit).astype(jnp.int32)
            n_l1 = jnp.sum(probe.l1_hit).astype(jnp.int32)
            n_local = jnp.sum(probe.local).astype(jnp.int32)
            cstats = CacheStats(
                n_hits=n_hits, n_misses=n_routed, n_inserted=n_ins,
                bytes_saved=(n_l1 + n_local) * row_bytes,
                n_local_hits=n_local,
                n_shard_hits=n_hits - n_l1 - n_local, n_l1_hits=n_l1,
                n_probe_demoted=probe.wire.n_demoted,
                probe_hit_peak=probe.wire.hit_peak,
                n_l3_hits=jnp.int32(0))
        n_unique = n_routed          # ids that went to their owner
    else:
        out_u, served_u = fetched, served_r
    with _stage("slot_scatter"):
        if dedup:
            out = out_u[inverse]
            # a dropped unique id zero-fills EVERY duplicate slot it
            # backed — count affected request slots, not wire slots
            dropped = jnp.sum(~served_u[inverse])
        else:
            out = out_u
            dropped = jnp.sum(~served_u)
    stats = FetchStats(jnp.int32(r), jnp.int32(n_unique),
                       dropped.astype(jnp.int32),
                       jnp.int32(probe.wire.probe_bytes if tier is not None
                                 else 0),
                       jnp.int32(0))
    if cache is not None:
        return out, new_cache, stats, cstats
    if return_stats:
        return out, stats
    return out


def _worker_generate(
    indptr: jax.Array,       # [N+1] local CSR
    indices: jax.Array,      # [E_pad]
    x_local: jax.Array,      # [rows, D] node features (row-sharded)
    y_local: jax.Array,      # [rows, 1] labels (row-sharded)
    seeds: jax.Array,        # [b] seeds owned by this worker (balance table row)
    rng: jax.Array,
    cache: Optional[FeatureCache] = None,   # per-worker hot-node cache state
    *,
    fanouts: Tuple[int, ...],
    axis_name: str,
    merge_mode: str = "butterfly",
    capacity_slack: float = 2.0,
    cache_cfg: Optional[CacheConfig] = None,
    fetch_capacity: Optional[int] = None,
    feature_store: str = "device",
    feat_dim: Optional[int] = None,
    host_admit=None,         # (ids [S], rows [S, D]) landed one step ago
    collect_stats: bool = False,
):
    """One worker's slice of an L-hop generation round (runs in shard_map).

    Per hop: broadcast frontier -> ``local_candidates`` scan -> tree merge
    (butterfly allreduce or recursive-halving reduce-scatter); the merged
    global sample becomes the next frontier.  Masks chain so a padded
    parent's subtree stays padded.  Then one deduplicated feature shuffle
    fetches every node's row, probing the hot-node cache tier first when
    one is threaded in — locally in replicated mode, via the two-stage
    shard routing in sharded mode (returns ``(SubgraphBatch, new_cache)``
    in either case).  ``cache_cfg`` is the single source of cache policy;
    ``fetch_capacity`` pins the owner-exchange buffer size (the warm
    re-calibration hook shrinks it to the steady-state miss count).

    With ``feature_store="host"`` the feature table lives in host RAM
    behind the L3 store: ``x_local`` is ``None`` (``feat_dim`` supplies
    the row width), the feature shuffle STAGES its cache misses instead
    of owner-fetching them, and the returns grow a ``HostMissRequest``
    tail — ``(batch, cache, req)`` cached / ``(batch, req)`` uncached.
    The batch's staged feature slots are zero holes until the caller
    patches them with the landed host gather (``patch_batch``); labels
    stay device-resident either way.

    With ``collect_stats=True`` (the autotuner's trace seam) the return
    grows a ``(FetchStats, CacheStats)`` tail: the feature shuffle's
    per-worker telemetry, normally folded into the few ``SubgraphBatch``
    counters, rides out whole so the trace recorder can keep per-step
    records.  Uncached runs ship a synthesized ``CacheStats`` whose only
    nonzero field is the conservation remainder (``n_misses`` for the
    device store, ``n_l3_hits`` for staged host fetches), so the
    invariant ``n_l1 + n_local + n_shard + n_l3 + n_misses ==
    n_distinct`` holds for every traced configuration.
    """
    b = seeds.shape[0]
    with _stage("edge_scan"):
        me = lax.axis_index(axis_name)
        rng = jax.random.fold_in(rng, me)
        hop_rngs = jax.random.split(rng, max(len(fanouts), 2))

    with _stage("frontier"):
        frontier = lax.all_gather(seeds, axis_name, tiled=True)  # [B]
        parent_mask = jnp.ones(frontier.shape, jnp.bool_)
    hops, masks = [], []
    shape = (b,)                # local tree shape accumulator
    local_rows = b              # b * k_1 * ... * k_l (this worker's rows)
    for level, k in enumerate(fanouts):
        with _stage("edge_scan"):
            cand = local_candidates(indptr, indices, frontier, k,
                                    hop_rngs[level])
            # padding must not spawn children:
            cand = Candidates(
                ids=cand.ids,
                keys=jnp.where(parent_mask[:, None], cand.keys, jnp.inf),
            )
        if merge_mode == "reduce_scatter":
            # beyond-paper: recursive-halving merge — each worker
            # materializes only ITS segment of the frontier
            # (tree_reduce.py); ~4x less ICI traffic than the butterfly
            # at W=16.
            with _stage("tree_merge"):
                seg = tree_reduce_scatter(cand, merge_topk, axis_name)
                m = jnp.isfinite(seg.keys)                    # [rows_l, k]
                h = jnp.where(m, seg.ids, 0)
            # the next frontier must still be GLOBAL (edge-centric: every
            # worker scans its local edges against all hop-l nodes)
            with _stage("frontier"):
                h_all = lax.all_gather(h, axis_name, tiled=True)
                m_all = lax.all_gather(m, axis_name, tiled=True)
        else:
            with _stage("tree_merge"):
                merged = tree_allreduce(cand, merge_topk, axis_name)
                m_all = jnp.isfinite(merged.keys)             # [F, k]
                h_all = jnp.where(m_all, merged.ids, 0)
                h = lax.dynamic_slice_in_dim(h_all, me * local_rows,
                                             local_rows, 0)
                m = lax.dynamic_slice_in_dim(m_all, me * local_rows,
                                             local_rows, 0)
        shape = shape + (k,)
        hops.append(h.reshape(shape))
        masks.append(m.reshape(shape))
        frontier = h_all.reshape(-1)                          # [F * k]
        parent_mask = m_all.reshape(-1)
        local_rows *= k

    # chain masks explicitly (the +inf-key propagation already implies this;
    # keep the invariant structural, not sampler-dependent)
    with _stage("tree_merge"):
        for level in range(1, len(masks)):
            masks[level] = jnp.logical_and(masks[level],
                                           masks[level - 1][..., None])

    # --- feature shuffle: one deduplicated fetch for every node slot,
    # cache-probed first when a hot-node cache is threaded through ---
    with _stage("dedup"):
        need = jnp.concatenate([seeds] + [h.reshape(-1) for h in hops])
    host = feature_store == "host"
    req = None
    if cache is not None and host:
        feats, cache, fstats, cstats, req = fetch_rows(
            x_local, need, axis_name, capacity_slack=capacity_slack,
            capacity=fetch_capacity, cache=cache, cache_cfg=cache_cfg,
            store="host", feat_dim=feat_dim, host_admit=host_admit)
        n_hits, n_misses = cstats.n_hits, cstats.n_misses
        n_demoted = cstats.n_probe_demoted
    elif cache is not None:
        feats, cache, fstats, cstats = fetch_rows(
            x_local, need, axis_name, capacity_slack=capacity_slack,
            capacity=fetch_capacity, cache=cache, cache_cfg=cache_cfg,
            store="device")
        n_hits, n_misses = cstats.n_hits, cstats.n_misses
        n_demoted = cstats.n_probe_demoted
    elif host:
        feats, fstats, req = fetch_rows(
            x_local, need, axis_name, capacity_slack=capacity_slack,
            capacity=fetch_capacity, store="host", feat_dim=feat_dim)
        n_hits, n_misses = jnp.int32(0), fstats.n_unique
        n_demoted = jnp.int32(0)
    else:
        feats, fstats = fetch_rows(x_local, need, axis_name,
                                   capacity_slack=capacity_slack,
                                   capacity=fetch_capacity,
                                   return_stats=True)
        n_hits, n_misses = jnp.int32(0), fstats.n_unique
        n_demoted = jnp.int32(0)
    d = x_local.shape[1] if x_local is not None else feat_dim
    with _stage("slot_scatter"):
        x_seed = feats[:b]
        x_hops = []
        off = b
        n = b
        for level, k in enumerate(fanouts):
            n *= k
            x = feats[off:off + n].reshape(masks[level].shape + (d,))
            x_hops.append(x * masks[level][..., None])
            off += n
    # balance-table seeds are already distinct per worker — skip the dedup
    # front end for the label fetch
    with _stage("labels"):
        ys, ystats = fetch_rows(y_local, seeds, axis_name,
                                capacity_slack=capacity_slack, dedup=False,
                                return_stats=True)
        labels = ys[:, 0].astype(jnp.int32)

    with _stage("slot_scatter"):
        n_dropped = (fstats.n_dropped + ystats.n_dropped)[None]
    batch = SubgraphBatch(
        seeds=seeds,
        hops=tuple(hops),
        masks=tuple(masks),
        x_seed=x_seed,
        x_hops=tuple(x_hops),
        labels=labels,
        n_dropped=n_dropped,
        n_cache_hits=n_hits[None],
        n_cache_misses=n_misses[None],
        n_probe_demoted=n_demoted[None],
    )
    if collect_stats:
        if cache is None:
            # synthesize the cache-tier view of an uncached fetch so the
            # trace's conservation check holds: every distinct id either
            # routed to its owner (device store -> n_misses) or staged
            # for the L3 gather (host store -> n_l3_hits)
            z = jnp.int32(0)
            cstats = CacheStats(
                n_hits=z, n_misses=z if host else fstats.n_unique,
                n_inserted=z, bytes_saved=z, n_local_hits=z,
                n_shard_hits=z, n_l1_hits=z, n_probe_demoted=z,
                probe_hit_peak=z,
                n_l3_hits=fstats.n_unique if host else z)
        stats = (fstats, cstats)
        if cache is not None and req is not None:
            return batch, cache, req, stats
        if cache is not None:
            return batch, cache, stats
        if req is not None:
            return batch, req, stats
        return batch, stats
    if cache is not None and req is not None:
        return batch, cache, req
    if cache is not None:
        return batch, cache
    if req is not None:
        return batch, req
    return batch


def make_former_generator_fn(mesh, **kwargs):
    """``generation.make_generator_fn(mesh, **kwargs)`` with the former
    ``_worker_generate`` (and so the former fetch) in each worker."""
    with mock.patch.object(generation, "_worker_generate", _worker_generate):
        return generation.make_generator_fn(mesh, **kwargs)
