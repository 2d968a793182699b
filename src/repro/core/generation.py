"""Distributed Subgraph Generation (paper §2 step 3) — edge-centric, in JAX.

The paper's MapReduce formulation: every worker scans *its own edge
partition* against the current frontier in parallel (edge-centric — hot
nodes parallelize because their edge lists are split across partitions),
then partial per-seed subgraphs are aggregated through a **tree reduction**
to the seed's owner.

TPU-native mapping (DESIGN.md §2), generalized to arbitrary-depth fanout
trees driven by ``fanouts = (k_1, ..., k_L)``:

  1. frontier broadcast     — ``lax.all_gather`` of owned seeds; after each
                              hop the merged sample becomes the next global
                              frontier (every worker scans its local edges
                              against ALL frontier nodes — edge-centric).
  2. local edge scan        — each worker samples ``k_l`` candidate
                              neighbors per frontier node from its local CSR
                              (a pure gather over the local edge array:
                              fully parallel, no hot-node serialization).
                              Padded parents carry ``+inf`` keys, so they
                              never spawn children — masks chain down the
                              tree.
  3. tree aggregation       — candidates carry *weighted reservoir keys*
                              (exponential race, A-ES scheme): the merge
                              "keep the k smallest keys" is associative, so
                              the butterfly ``tree_allreduce`` (or the
                              recursive-halving ``tree_reduce_scatter``)
                              yields a weighted sample of the UNION of all
                              workers' local edges — i.e. a uniform fanout
                              sample of the global neighborhood.
  4. feature shuffle        — dense node features are fetched from their
                              owner workers with a routed ``all_to_all``
                              exchange (the MapReduce shuffle).  The tree
                              contains the same node id many times (hot
                              neighbors, with-replacement sampling), so the
                              shuffle is **request-deduplicated**: each
                              distinct id crosses the interconnect once and
                              the fetched row is scattered back to every
                              slot that asked for it.  In front of the
                              all_to_all sits an optional **device-resident
                              hot-node cache** (core/feature_cache.py):
                              distinct ids are first probed against the
                              cache tier and only the *misses* are routed —
                              hot rows that recur across iterations stop
                              being fetched from their owners, and served
                              misses are admitted back (frequency
                              admission) so the cache tracks the workload.
                              Requests beyond the per-destination capacity
                              are *counted* (``SubgraphBatch.n_dropped``),
                              never silently zero-filled, and cache
                              hits/misses surface as
                              ``SubgraphBatch.n_cache_hits/n_cache_misses``.

**Mode-polymorphic cache-aware routing** (``CacheConfig.mode``): the
replicated cache caps total distinct capacity at ~C no matter how many
workers join (every replica converges on the same Zipf head); sharded
mode partitions the id-space over the worker axis (capacity x W); tiered
mode composes both.  Each mode is a (probe, admit) strategy pair — the
fetch path itself never branches on the mode.  The full three-stage
tiered flow (the other modes run a subset of it):

  stage 0 (L1 probe)     — every deduplicated id is probed against the
           LOCAL replicated L1 (the global Zipf head, ``l1_rows`` slots).
           An L1 hit costs zero network — it skips the probe round AND
           the owner fetch.  [tiered only]
  stage 1 (shard probe)  — the remaining ids are routed to their
           *cache-shard* worker (``shard_of(id, W)``) with one
           ``all_to_all`` probe round; the shard holder probes its local
           tier and responds — DistDGL-style "ask the worker whose CACHE
           holds a hot row, not its owner".  The RESPONSE rides one of
           two wire formats (``CacheConfig.wire``): **dense** ships the
           full ``[W, cap, D]`` row block back even though only hit
           slots carry data, **compact** (the default) ships a packed
           hit bitmap plus a row payload compacted to ``hit_cap`` rows
           per destination — stage-1 bytes then scale with *hits*, not
           with the probe capacity.  In tiered mode the round carries
           only L1 *misses*, so its wire bytes shrink by the L1 hit
           fraction — and the compact payload compounds the saving
           (fewer probe hits support a tighter ``hit_cap``).
           [sharded + tiered]
  stage 2 (owner fetch)  — only shard-*misses* fall through to the routed
           owner fetch; the served rows then ride one more ``all_to_all``
           back to the shard holders (reusing the probe round's slot
           assignment) so admission updates the AUTHORITATIVE shard, not a
           local replica.  In tiered mode every row the L2 tier SERVED the
           requester this round is also OFFERED to its local L1, which
           installs it after ``l1_promote`` observations — the hottest
           rows migrate L2 -> L1 on every worker without any broadcast
           (owner-fetched rows are not offered: the cold tail must not
           churn the small L1's admission tags).  [all modes; replicated
           probes/admits locally]

A shard hit's row still crosses the wire (shard holder -> requester
instead of owner -> requester), so ``CacheStats`` splits the hits into
``n_l1_hits`` (zero network) / ``n_local_hits`` (own shard, no crossing) /
``n_shard_hits`` (remote shard) and ``bytes_saved`` counts only the first
two.  Cached fetches stay bit-identical to uncached fetches in every mode
— cached rows are verbatim table copies wherever they live.

Edges sampled for several seeds are *replicated* into each seed's subgraph
(paper step 3), which falls out of sampling per frontier slot.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..graph.subgraph import SubgraphBatch
from .feature_cache import (CacheConfig, CacheStats, FeatureCache,
                            TieredCache, cache_insert, cache_probe,
                            compact_hit_rows, expand_hit_rows,
                            get_probe_impl, hit_bitmap_words,
                            init_cache_state, pack_hit_bitmap,
                            restore_worker_axis, shard_of,
                            squeeze_worker_axis, tiered_probe,
                            unpack_hit_bitmap)
from .host_store import HostFeatureStore, HostMissRequest
from .partition import PartitionedGraph
from .tree_reduce import tree_allreduce, tree_reduce_scatter

#: The generator's stages, each a ``jax.named_scope`` around the code that
#: does its work (``_stage``).  An operation belongs to the OUTERMOST stage
#: in its name stack, so the stages partition the device time of
#: ``jit(gen_fn)``: the label fetch's gather counts under ``labels``, not
#: ``owner_fetch``.  Scopes are metadata only; the compiled instructions
#: are the same without them.
STAGES = ("frontier", "edge_scan", "tree_merge", "dedup", "cache_probe",
          "owner_fetch", "cache_insert", "slot_scatter", "labels")


def _stage(name: str):
    """The named scope of generation stage ``name`` (one of ``STAGES``)."""
    if name not in STAGES:
        raise ValueError(f"unknown generation stage {name!r}; "
                         f"expected one of {STAGES}")
    return jax.named_scope(name)


def _staged(name: str):
    """Decorator: run the function inside ``_stage(name)``, entered anew
    on each call (one scope object shared by every call keeps the name
    stack it replaced on itself, so it is not re-entrant)."""
    _stage(name)

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with _stage(name):
                return fn(*args, **kwargs)
        return run
    return wrap


class Candidates(NamedTuple):
    ids: jax.Array    # [F, k] neighbor node ids
    keys: jax.Array   # [F, k] reservoir keys (+inf = invalid)


class FetchStats(NamedTuple):
    """Telemetry from one ``fetch_rows`` shuffle (per-worker scalars).

    ``probe_round_bytes`` is MEASURED, not estimated: it is the byte size
    of the buffers this worker actually ships on the stage-1 shard-probe
    round (ids up, plus the hit/row response down — dense or compact per
    ``CacheConfig.wire``), computed from the static exchange shapes the
    compiled program moves.  It is 0 whenever no probe round runs
    (uncached, replicated mode, or W == 1); summing it over workers and
    iterations gives the total probe-round wire volume a run paid.

    ``host_gather_bytes`` is the PCIe payload of the L3 staging round a
    ``store="host"`` fetch hands to the host store (staged miss ids up
    plus the landed feature rows back down, from the static staging
    shape) — 0 whenever the feature table is device-resident."""
    n_requests: jax.Array   # request slots presented (incl. duplicates)
    n_unique: jax.Array     # distinct ids actually routed over the wire
    n_dropped: jax.Array    # request SLOTS zero-filled by the capacity
                            # bound (a dropped unique id counts once per
                            # duplicate slot it would have served)
    probe_round_bytes: jax.Array
                            # bytes this worker shipped on the shard-probe
                            # all_to_all round (0 = no probe round ran)
    host_gather_bytes: jax.Array
                            # bytes of the host-store staging round trip
                            # (0 = device-resident feature table)

    @classmethod
    def zero(cls) -> "FetchStats":
        """An all-zero ``FetchStats`` (python ints — combines with either
        host-side window accumulators or device scalars)."""
        return cls(*(0,) * len(cls._fields))

    def combine(self, other: "FetchStats") -> "FetchStats":
        """Merge two windows' fetch telemetry into one window's.

        Every ``FetchStats`` field is additive (counts and byte totals),
        so a window's stats are the fold of its per-step records — the
        per-window stat-splitting primitive the trace recorder
        (``launch/autotune.py``) uses to separate the cold burst from
        the warm steady state without re-measuring either."""
        return FetchStats(*(a + b for a, b in zip(self, other)))


def local_candidates(
    indptr: jax.Array,
    indices: jax.Array,
    frontier: jax.Array,
    k: int,
    rng: jax.Array,
) -> Candidates:
    """Sample ``k`` neighbors-with-replacement of each frontier node from a
    local CSR partition, tagged with weighted reservoir keys.

    Each draw represents ``deg_local / k`` edges, so its key is an
    Exponential(rate = deg_local / k) variate — the min-k merge over workers
    is then a weighted (≈ uniform-over-global-edges) sample of the union.
    """
    f = frontier.shape[0]
    node = jnp.clip(frontier, 0, indptr.shape[0] - 2)
    start = indptr[node]
    deg = (indptr[node + 1] - start).astype(jnp.int32)
    r_off, r_key = jax.random.split(rng)
    offs = jax.random.randint(r_off, (f, k), 0, jnp.iinfo(jnp.int32).max)
    offs = offs % jnp.maximum(deg, 1)[:, None]
    ids = indices[jnp.clip(start[:, None] + offs, 0, indices.shape[0] - 1)]
    u = jax.random.uniform(r_key, (f, k), minval=jnp.finfo(jnp.float32).tiny)
    weight = (deg.astype(jnp.float32) / k)[:, None]
    keys = -jnp.log(u) / jnp.maximum(weight, 1e-30)
    keys = jnp.where((deg > 0)[:, None], keys, jnp.inf)
    return Candidates(ids=ids.astype(jnp.int32), keys=keys)


def merge_topk(a: Candidates, b: Candidates) -> Candidates:
    """Associative merge: keep the k smallest keys of the union."""
    k = a.keys.shape[-1]
    keys = jnp.concatenate([a.keys, b.keys], axis=-1)
    ids = jnp.concatenate([a.ids, b.ids], axis=-1)
    neg, idx = lax.top_k(-keys, k)
    return Candidates(ids=jnp.take_along_axis(ids, idx, axis=-1), keys=-neg)


@_staged("dedup")
def dedup_requests(ids: jax.Array):
    """Static-shape sort+segment unique (``jnp.unique`` needs dynamic sizes).

    Returns ``(uniq, inverse, valid, n_unique)`` where ``uniq`` is a [R]
    array whose first ``n_unique`` slots hold the distinct ids (the tail is
    unspecified padding), ``inverse`` maps each original slot to its unique
    slot (``uniq[inverse] == ids``), and ``valid[i] = i < n_unique``.
    """
    r = ids.shape[0]
    if r == 0:
        # the group-start marker below concatenates a length-1 sentinel,
        # which has no length-0 analogue — an empty batch has no uniques
        return (ids, jnp.zeros((0,), jnp.int32),
                jnp.zeros((0,), jnp.bool_), jnp.int32(0))
    order = jnp.argsort(ids)
    s = ids[order]
    is_first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), s[1:] != s[:-1]])
    group = (jnp.cumsum(is_first) - 1).astype(jnp.int32)     # [R], sorted
    n_unique = group[-1] + 1
    uniq = jnp.zeros((r,), ids.dtype).at[group].set(s)
    inverse = jnp.zeros((r,), jnp.int32).at[order].set(group)
    valid = jnp.arange(r, dtype=jnp.int32) < n_unique
    return uniq, inverse, valid, n_unique


def probe_round_capacity(n_requests: int, n_workers: int,
                         capacity_slack: float = 2.0) -> int:
    """Per-destination slot count of the slack-sized exchange rounds.

    THE sizing formula ``fetch_rows`` uses for the owner exchange (before
    dedup clamping / explicit ``capacity``) and for the shard-probe round
    (always — the probe round carries ALL distinct ids, see
    ``fetch_rows``): ``min(R, ceil(R / W) * slack + 8)``.  Exposed so the
    launcher's hit-cap calibration derives its ladder rungs from the SAME
    capacity the compiled fetch will use — a reimplementation that
    drifted would calibrate a bound for buffers that do not exist."""
    return int(min(n_requests,
                   -(-n_requests // n_workers) * capacity_slack + 8))


class _RoutePlan(NamedTuple):
    """Per-destination slot assignment of one routed all_to_all round.

    The assignment is a pure function of ``(dest, cap)`` — the shard-probe
    and shard-admission rounds rely on this determinism to reuse ONE plan,
    so the rows a requester sends for admission land exactly on the recv
    slots whose ids the shard holder probed.
    """
    order: jax.Array        # [R] argsort of dest (requests in send order)
    sorted_dest: jax.Array  # [R] dest[order] (w = sentinel "nowhere")
    slot_c: jax.Array       # [R] per-destination slot, cap = overflow/drop
    ok: jax.Array           # [R] request got a wire slot (in sorted order)


def _route_plan(dest: jax.Array, cap: int, w: int) -> _RoutePlan:
    """Assign each request a (destination, slot) wire position.

    ``dest == w`` is the sentinel for requests that must not cross the
    interconnect; requests beyond ``cap`` per destination overflow to slot
    index ``cap`` so a ``mode="drop"`` scatter discards them (clipping
    would overwrite the request already in the last slot).
    """
    r = dest.shape[0]
    order = jnp.argsort(dest)
    sorted_dest = dest[order]
    first = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
    slot = jnp.arange(r, dtype=jnp.int32) - first
    ok = jnp.logical_and(slot < cap, sorted_dest < w)
    slot_c = jnp.where(ok, slot, cap)
    return _RoutePlan(order, sorted_dest, slot_c, ok)


def _routed_fetch(
    table_local: jax.Array,
    ids: jax.Array,
    valid: jax.Array,
    axis_name: str,
    cap: int,
    w: int,
    rows: int,
):
    """One routed all_to_all round trip serving ``ids[valid]`` requests.

    Returns ``(rows [R, D], served [R])`` — invalid slots return zero rows
    with ``served=False``; valid slots beyond the per-destination capacity
    ``cap`` also return zero rows with ``served=False`` (the caller decides
    what counts as a drop).
    """
    r = ids.shape[0]
    owner = jnp.clip(ids // rows, 0, w - 1)
    # invalid slots route to a sentinel bucket past the last worker so they
    # neither consume capacity nor cross the interconnect
    owner = jnp.where(valid, owner, w)
    plan = _route_plan(owner, cap, w)
    send = jnp.zeros((w, cap), dtype=jnp.int32)
    send = send.at[plan.sorted_dest, plan.slot_c].set(ids[plan.order],
                                                      mode="drop")
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    me = lax.axis_index(axis_name)
    local = jnp.clip(recv - me * rows, 0, rows - 1)
    served = table_local[local]                      # [w, cap, D]
    resp = lax.all_to_all(served, axis_name, split_axis=0, concat_axis=0, tiled=True)
    got = resp[jnp.clip(plan.sorted_dest, 0, w - 1),
               jnp.clip(plan.slot_c, 0, cap - 1)]
    got = jnp.where(plan.ok[:, None], got, 0)
    out = jnp.zeros((r, table_local.shape[1]), table_local.dtype)
    served = jnp.zeros((r,), jnp.bool_).at[plan.order].set(plan.ok)
    return out.at[plan.order].set(got), served


class _WireStats(NamedTuple):
    """Holder-side probe-round telemetry one ``_shard_probe`` produces.

    ``n_demoted``/``hit_peak`` are per-worker int32 scalars (see
    ``CacheStats``); ``probe_bytes`` is the MEASURED per-worker byte cost
    of the round — a static python int derived from the exchange buffer
    shapes the compiled program actually ships."""
    n_demoted: jax.Array    # hits the compact hit_cap bound demoted
    hit_peak: jax.Array     # max per-destination hits before demotion
    probe_bytes: int        # bytes this worker ships on the round


def probe_hit_cap(cfg: CacheConfig, cap: int) -> int:
    """Resolved compact-wire payload bound for a probe capacity ``cap``.

    ``CacheConfig.hit_cap == 0`` auto-sizes to half the probe capacity —
    a conservative 2x response-row saving that never demotes while fewer
    than half the probe slots hit; an explicit (calibrated) ``hit_cap``
    is clamped into ``[1, cap]``."""
    return max(min(cfg.hit_cap or max(cap // 2, 1), cap), 1)


def _shard_probe(
    cache: FeatureCache,
    cfg: CacheConfig,
    ids: jax.Array,
    valid: jax.Array,
    axis_name: str,
    cap: int,
    w: int,
):
    """Stage-1 routing: probe each id against its CACHE-SHARD worker.

    One all_to_all round trip — ids ride to their shard holders, every
    holder probes its local shard for everything it received, and the
    response rides back in the wire format ``cfg.wire`` selects:

      dense    — ``(hit [w, cap] bool, rows [w, cap, D])``: every probe
                 slot ships a row slot back, hit or not.
      compact  — ``(bitmap [w, words] uint32, payload [w, hit_cap, D])``:
                 one bit per probe slot plus only the hit rows, compacted
                 in slot order by the holder (``compact_hit_rows``) and
                 re-expanded by the requester via the bitmap's prefix
                 sums (``expand_hit_rows``) — bit-identical to the dense
                 response for every surviving hit.  Hits beyond
                 ``hit_cap`` per destination are DEMOTED to misses by
                 the holder (bit cleared), falling through to the owner
                 fetch exactly like probe-capacity overflow.

    Returns ``(hit [R], rows [R, D], plan, recv_ids [w, cap], wire)``
    where ``wire`` is the ``_WireStats`` telemetry; ids beyond the probe
    capacity simply miss (they fall through to the owner fetch — a lost
    hit opportunity, never a correctness loss).  ``plan``/``recv_ids``
    feed ``_shard_admit`` so the admission round reuses this round's
    slot assignment.
    """
    r = ids.shape[0]
    dest = jnp.where(valid, shard_of(ids, w), w)
    plan = _route_plan(dest, cap, w)
    # empty probe slots carry -1, which the probe masks out (node ids are
    # always >= 0, so -1 can never alias a resident key)
    send = jnp.full((w, cap), -1, jnp.int32)
    send = send.at[plan.sorted_dest, plan.slot_c].set(ids[plan.order],
                                                      mode="drop")
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0, tiled=True)
    flat = recv.reshape(-1)
    d = cache.rows.shape[-1]
    item = jnp.dtype(cache.rows.dtype).itemsize
    probe_bytes = w * cap * 4                    # ids up, int32
    if cfg.wire == "compact":
        hc = probe_hit_cap(cfg, cap)
        n_words = hit_bitmap_words(cap)
        if get_probe_impl() == "pallas":
            # fused probe+compact: never materializes the dense [w, cap, D]
            # response block the compact wire exists to not ship; the
            # raw (pre-demotion) bitmap rides along as a second kernel
            # output, so ONE probe serves both the wire and the
            # demotion/hit-peak telemetry
            from ..kernels.ops import cache_probe_compact
            words, raw_words, payload = cache_probe_compact(
                cache.keys, cache.rows, recv, assoc=cfg.assoc, hit_cap=hc,
                use_kernel=True)
            kept = unpack_hit_bitmap(words, cap)
            raw_hit = unpack_hit_bitmap(raw_words, cap)
        else:
            hit_f, rows_f = cache_probe(cache, flat, valid=flat >= 0,
                                        cfg=cfg)
            raw_hit = hit_f.reshape(w, cap)
            kept, payload = compact_hit_rows(raw_hit,
                                             rows_f.reshape(w, cap, d), hc)
            words = pack_hit_bitmap(kept)
        wire = _WireStats(
            n_demoted=jnp.sum(jnp.logical_and(raw_hit, ~kept))
            .astype(jnp.int32),
            hit_peak=jnp.max(jnp.sum(raw_hit, axis=1)).astype(jnp.int32),
            probe_bytes=probe_bytes + w * n_words * 4 + w * hc * d * item)
        words_b = lax.all_to_all(words, axis_name,
                                 split_axis=0, concat_axis=0, tiled=True)
        pay_b = lax.all_to_all(payload, axis_name,
                               split_axis=0, concat_axis=0, tiled=True)
        hit_b = unpack_hit_bitmap(words_b, cap)
        rows_b = expand_hit_rows(hit_b, pay_b)
        row_dtype = payload.dtype
    else:
        hit_f, rows_f = cache_probe(cache, flat, valid=flat >= 0, cfg=cfg)
        hit2 = hit_f.reshape(w, cap)
        wire = _WireStats(
            n_demoted=jnp.int32(0),
            hit_peak=jnp.max(jnp.sum(hit2, axis=1)).astype(jnp.int32),
            probe_bytes=probe_bytes + w * cap * 1 + w * cap * d * item)
        hit_b = lax.all_to_all(hit2, axis_name,
                               split_axis=0, concat_axis=0, tiled=True)
        rows_b = lax.all_to_all(rows_f.reshape(w, cap, d), axis_name,
                                split_axis=0, concat_axis=0, tiled=True)
        row_dtype = rows_f.dtype
    g = (jnp.clip(plan.sorted_dest, 0, w - 1), jnp.clip(plan.slot_c, 0, cap - 1))
    got_hit = jnp.logical_and(hit_b[g], plan.ok)
    got_rows = jnp.where(got_hit[:, None], rows_b[g], 0)
    hit = jnp.zeros((r,), jnp.bool_).at[plan.order].set(got_hit)
    hit_rows = jnp.zeros((r, d), row_dtype).at[plan.order].set(got_rows)
    return hit, hit_rows, plan, recv, wire


def _shard_admit(
    cache: FeatureCache,
    cfg: CacheConfig,
    plan: _RoutePlan,
    recv_ids: jax.Array,
    fetched: jax.Array,
    should: jax.Array,
    axis_name: str,
    w: int,
):
    """Stage-2 write-back: offer owner-fetched rows to their shard holders.

    Reuses the probe round's slot assignment, so the shard holder pairs
    each incoming row with the id it probed at that slot — admission
    updates the AUTHORITATIVE shard, not the requester's local state.
    Returns ``(new_cache, n_inserted)`` for THIS worker's shard.
    """
    cap = recv_ids.shape[1]
    d = fetched.shape[1]
    send_rows = jnp.zeros((w, cap, d), fetched.dtype)
    send_rows = send_rows.at[plan.sorted_dest, plan.slot_c].set(
        fetched[plan.order], mode="drop")
    send_should = jnp.zeros((w, cap), jnp.bool_)
    send_should = send_should.at[plan.sorted_dest, plan.slot_c].set(
        should[plan.order], mode="drop")
    recv_rows = lax.all_to_all(send_rows, axis_name,
                               split_axis=0, concat_axis=0, tiled=True)
    recv_should = lax.all_to_all(send_should, axis_name,
                                 split_axis=0, concat_axis=0, tiled=True)
    ids_f = recv_ids.reshape(-1)
    offer = jnp.logical_and(recv_should.reshape(-1), ids_f >= 0)
    return cache_insert(cache, ids_f, recv_rows.reshape(-1, d), offer, cfg)


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


@_staged("cache_insert")
def _host_admit(cache, cfg: CacheConfig, adm_ids: jax.Array,
                adm_rows: jax.Array, axis_name: str, w: int):
    """Deferred admission: offer the PREVIOUS step's landed L3 rows.

    With ``store="host"`` the owner fetch never runs, so the cache admits
    the rows the host gather landed one step later (``host_admit=``) —
    the same frequency-admission policy, shifted by the double buffer's
    one-step lag.  Sharded/tiered W > 1 route each row to its cache-shard
    holder first (one all_to_all round, same "admit the AUTHORITATIVE
    shard" rule as ``_shard_admit``); tiered admits into the L2 — the
    L1 sees rows only via the usual L2 -> L1 promotion at probe time.
    Returns ``(new_cache, n_inserted, admit_round_bytes)``.
    """
    s = adm_ids.shape[0]
    d = adm_rows.shape[1]
    if cfg.mode == "tiered":
        target, tcfg = cache.l2, cfg.l2_config()
    else:
        target, tcfg = cache, cfg
    if w == 1 or cfg.mode == "replicated":
        new, n_ins = cache_insert(target, adm_ids, adm_rows,
                                  adm_ids >= 0, tcfg)
        adm_bytes = 0
    else:
        dest = jnp.where(adm_ids >= 0, shard_of(adm_ids, w), w)
        plan = _route_plan(dest, s, w)   # cap = s: routing never overflows
        send_ids = jnp.full((w, s), -1, jnp.int32)
        send_ids = send_ids.at[plan.sorted_dest, plan.slot_c].set(
            adm_ids[plan.order], mode="drop")
        send_rows = jnp.zeros((w, s, d), adm_rows.dtype)
        send_rows = send_rows.at[plan.sorted_dest, plan.slot_c].set(
            adm_rows[plan.order], mode="drop")
        recv_ids = lax.all_to_all(send_ids, axis_name,
                                  split_axis=0, concat_axis=0, tiled=True)
        recv_rows = lax.all_to_all(send_rows, axis_name,
                                   split_axis=0, concat_axis=0, tiled=True)
        flat = recv_ids.reshape(-1)
        new, n_ins = cache_insert(target, flat, recv_rows.reshape(-1, d),
                                  flat >= 0, tcfg)
        adm_bytes = w * s * (4 + d * jnp.dtype(adm_rows.dtype).itemsize)
    if cfg.mode == "tiered":
        return TieredCache(l1=cache.l1, l2=new), n_ins, adm_bytes
    return new, n_ins, adm_bytes


def _host_fetch(ids, axis_name, capacity_slack, capacity, cache, cache_cfg,
                host_admit, d, dtype, w):
    """The ``store="host"`` fetch body: probe tiers, STAGE misses for L3.

    Instead of the routed owner fetch, cache-tier misses are compacted
    into a per-worker staging buffer of ids handed back to the caller as
    a ``HostMissRequest`` — the host store gathers them asynchronously
    and the NEXT step consumes the landed rows (``patch_batch`` fills the
    holes, ``_host_admit`` feeds the cache).  Hit slots are served now;
    staged slots return zero-filled holes flagged ``req.patch``; misses
    beyond the staging capacity are dropped (counted, never silent).
    """
    r = ids.shape[0]
    s = capacity if capacity is not None \
        else probe_round_capacity(r, 1, capacity_slack)
    s = max(int(s), 1)
    req_ids, inverse, req_valid, n_distinct = dedup_requests(ids)
    n_adm = jnp.int32(0)
    adm_bytes = 0
    if cache is not None and host_admit is not None:
        adm_ids, adm_rows = host_admit
        cache, n_adm, adm_bytes = _host_admit(cache, cache_cfg, adm_ids,
                                              adm_rows, axis_name, w)
    tier = _cache_tier(cache_cfg) if cache is not None else None
    if tier is not None:
        probe = tier.probe(cache, cache_cfg, req_ids, req_valid, axis_name,
                           probe_round_capacity(r, w, capacity_slack), w)
        hit = probe.hit
    else:
        probe = None
        hit = jnp.zeros((r,), jnp.bool_)
    # --- stage the misses: compact them into the [S] id buffer ----------
    with _stage("owner_fetch"):
        miss = jnp.logical_and(req_valid, ~hit)
        cs = jnp.cumsum(miss.astype(jnp.int32))
        staged = jnp.logical_and(miss, cs <= s)
        slot_u = cs - 1                   # staging slot per unique slot
        miss_ids = jnp.full((s,), -1, jnp.int32)
        miss_ids = miss_ids.at[jnp.where(staged, slot_u, s)].set(
            req_ids, mode="drop")
        n_staged = jnp.sum(staged).astype(jnp.int32)
        n_overflow = jnp.sum(miss).astype(jnp.int32) - n_staged
    with _stage("slot_scatter"):
        if tier is not None:
            out_u = jnp.where(hit[:, None], probe.rows, 0)
        else:
            out_u = jnp.zeros((r, d), dtype)
        served_u = jnp.logical_or(hit, staged)
        out = out_u[inverse]
        dropped = jnp.sum(~served_u[inverse]).astype(jnp.int32)
        req = HostMissRequest(ids=miss_ids,
                              slot=slot_u[inverse].astype(jnp.int32),
                              patch=staged[inverse])
    gather_bytes = s * (4 + d * jnp.dtype(dtype).itemsize)
    stats = FetchStats(
        jnp.int32(r), n_staged, dropped,
        jnp.int32((probe.wire.probe_bytes if tier is not None else 0)
                  + adm_bytes),
        jnp.int32(gather_bytes))
    if tier is None:
        return out, stats, req
    # tiered L1 promotion still happens at probe time (L2-served rows)
    new_cache = cache
    n_ins = n_adm
    if cache_cfg.mode == "tiered":
        l2_hit = probe.ctx[2]
        with _stage("cache_insert"):
            new_l1, n_l1_ins = cache_insert(cache.l1, req_ids, probe.rows,
                                            l2_hit, cache_cfg.l1_config())
        new_cache = TieredCache(l1=new_l1, l2=cache.l2)
        n_ins = n_ins + n_l1_ins
    row_bytes = d * jnp.dtype(dtype).itemsize
    with _stage("cache_probe"):
        n_hits = jnp.sum(probe.hit).astype(jnp.int32)
        n_l1 = jnp.sum(probe.l1_hit).astype(jnp.int32)
        n_local = jnp.sum(probe.local).astype(jnp.int32)
        cstats = CacheStats(
            n_hits=n_hits, n_misses=n_overflow, n_inserted=n_ins,
            bytes_saved=(n_l1 + n_local) * row_bytes, n_local_hits=n_local,
            n_shard_hits=n_hits - n_l1 - n_local, n_l1_hits=n_l1,
            n_probe_demoted=probe.wire.n_demoted,
            probe_hit_peak=probe.wire.hit_peak,
            n_l3_hits=n_staged)
    return out, new_cache, stats, cstats, req


def fetch_rows(
    table_local: jax.Array,
    ids: jax.Array,
    axis_name: str,
    capacity_slack: float = 2.0,
    dedup: bool = True,
    capacity: Optional[int] = None,
    return_stats: bool = False,
    cache: Optional[FeatureCache] = None,
    cache_cfg: Optional[CacheConfig] = None,
    store: Optional[str] = None,
    feat_dim: Optional[int] = None,
    host_admit=None,
):
    """Routed remote row fetch (the MapReduce shuffle, as ``all_to_all``).

    ``table_local`` is this worker's [rows, D] block of a row-sharded table;
    global row ``i`` lives on worker ``i // rows``.  Every worker requests
    ``ids`` [R] and receives the corresponding rows [R, D].

    ``store`` picks where MISSES resolve (default: ``cache_cfg.store``,
    else ``"device"``).  With ``store="host"`` the owner fetch is
    replaced by the L3 *issue/collect* split (``core/host_store.py``):
    cache-tier misses are STAGED into a ``HostMissRequest`` appended to
    the return value (``(out, new_cache, FetchStats, CacheStats, req)``
    cached, ``(out, stats, req)`` uncached) instead of fetched — their
    output rows are zero holes the caller patches one step later with
    the landed host gather (``patch_batch``), and ``host_admit=(ids
    [S], rows [S, D])`` feeds the PREVIOUS step's landed buffer back
    into the cache (deferred admission, ``_host_admit``).  The host path
    requires ``dedup=True``; ``table_local`` may be ``None`` (there is
    no device table) when ``feat_dim`` supplies the row width, and
    ``capacity`` sizes the staging buffer (default: the slack formula
    with W = 1 — staging is per-worker, not per-destination).

    With ``dedup=True`` (default) duplicate ids are collapsed before
    routing: each distinct id occupies at most one wire slot and its row is
    scattered back to every requesting slot.  A fanout tree's request list
    is massively duplicated (hot neighbors, with-replacement sampling), so
    at a given per-destination capacity this slashes the drop rate — and
    because distinct requests per destination can never exceed the
    destination's ``rows``, the default capacity is clamped to ``rows``
    (shrinking the static exchange buffers).

    With ``cache`` (a per-worker ``FeatureCache``/``TieredCache``; requires
    dedup AND ``cache_cfg`` — the ``CacheConfig`` the state was populated
    under, since the slot layout is a property of the state) the distinct
    ids are first probed against the device-resident hot-node cache tier,
    through the mode's (probe, admit) strategy pair (``_CACHE_TIERS``):
    **replicated** probes locally; **sharded** (W > 1) rides one all_to_all
    probe round to the cache-shard workers; **tiered** probes the local
    replicated L1 first (zero network) and sends only L1 misses on the
    probe round — the three-stage flow in the module docstring.  In every
    mode only the cache-tier **misses** enter the owner all_to_all, the
    returned rows are bit-identical to the uncached path (cached rows are
    verbatim table copies), the return value becomes
    ``(out, new_cache, FetchStats, CacheStats)``, and ``n_unique`` counts
    only the ids that went to their owner.

    With ``cache_cfg.frozen`` (the read-mostly serve view,
    ``CacheConfig.serve_view()``) the probe stage runs unchanged but the
    admit stage is the identity: ``new_cache`` is the input state
    bit-for-bit, nothing is admitted or promoted, and the admission
    collectives drop out of the compiled program — the serving tier's
    request-path form.

    The shard-probe round's RESPONSE rides the wire format
    ``cache_cfg.wire`` selects: ``"dense"`` ships a full ``[W, cap, D]``
    row block back (every probe slot pays a row slot, hit or not);
    ``"compact"`` ships a packed hit bitmap plus a row payload bounded by
    ``probe_hit_cap(cache_cfg, cap)`` rows per destination, so stage-1
    bytes scale with hits instead of capacity (see ``_shard_probe``).
    Hits beyond the bound are demoted to owner-fetched misses
    (``CacheStats.n_probe_demoted``) — never a correctness loss.
    ``FetchStats.probe_round_bytes`` reports the bytes the chosen format
    actually shipped, measured from the static exchange buffer shapes.

    Per-destination OWNER capacity defaults to ``ceil(R/W) * slack``
    (clamped as above when dedup is on); pass an explicit ``capacity`` —
    e.g. sized to the steady-state cache-miss count by the warm
    re-calibration hook in ``launch/train.py`` — to shrink the static
    owner-exchange buffers below their cache-unaware cold-start size.  The
    sharded probe round keeps the slack-based size regardless: it carries
    ALL distinct ids (not just misses), so shrinking it with the miss rate
    would spill probes to the owner path and undo the hit rate it was
    sized for.  Requests beyond capacity return zero rows and are counted
    per request slot — pass ``return_stats=True`` to receive
    ``(out, FetchStats)`` instead of silently zero-filled rows.  For
    W == 1 the fetch degenerates to a local gather (no routing; sharded
    mode degenerates to replicated — the single worker owns every shard —
    and ``n_unique`` still reports the would-route distinct/miss count so
    single-device runs measure the same wire-slot telemetry).
    """
    if cache is not None and not dedup:
        raise ValueError("the cache front end requires dedup=True")
    if cache is not None and cache_cfg is None:
        # the slot layout and placement are properties of the POPULATED
        # state; guessing a default here would silently probe an assoc>1
        # or sharded cache with the wrong layout (near-zero hit rate, no
        # error) — the policy object must travel with the state
        raise ValueError("fetch_rows(cache=...) requires cache_cfg "
                         "(the CacheConfig the state was populated under)")
    if store is None:
        store = cache_cfg.store if cache_cfg is not None else "device"
    host = store == "host"
    if host and not dedup:
        raise ValueError('fetch_rows(store="host") requires dedup=True')
    if host and cache_cfg is not None and cache_cfg.frozen:
        raise ValueError('a frozen (read-mostly serve) cache cannot ride '
                         'the L3 staging path — serve misses resolve '
                         'against the device table (see serve_view())')
    if host and table_local is None and feat_dim is None:
        raise ValueError('fetch_rows(store="host") without a device table '
                         'requires feat_dim (the feature row width)')
    if not host and table_local is None:
        raise ValueError('fetch_rows(store="device") requires table_local')
    if not host and host_admit is not None:
        raise ValueError('host_admit only applies to store="host"')
    w = lax.axis_size(axis_name)
    d = table_local.shape[1] if table_local is not None else feat_dim
    dtype = table_local.dtype if table_local is not None else jnp.float32
    rows = table_local.shape[0] if table_local is not None else 0
    r = ids.shape[0]
    if r == 0:
        # empty request batch: nothing to route (uniform across workers —
        # the request shape is static — so skipping the collectives is
        # safe); counters are all zero by conservation
        out = jnp.zeros((0, d), dtype)
        stats = FetchStats(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                           jnp.int32(0), jnp.int32(0))
        if host:
            # deferred admission still runs (a landed buffer may be
            # pending even when this step requests nothing)
            n_adm = jnp.int32(0)
            if cache is not None and host_admit is not None:
                cache, n_adm, _ = _host_admit(cache, cache_cfg,
                                              host_admit[0], host_admit[1],
                                              axis_name, w)
            s0 = max(int(capacity), 1) if capacity is not None else 1
            req = HostMissRequest(jnp.full((s0,), -1, jnp.int32),
                                  jnp.zeros((0,), jnp.int32),
                                  jnp.zeros((0,), jnp.bool_))
            if cache is not None:
                z = jnp.int32(0)
                return out, cache, stats, CacheStats(
                    z, z, n_adm, z, z, z, z, z, z, z), req
            return out, stats, req
        if cache is not None:
            z = jnp.int32(0)
            return out, cache, stats, CacheStats(z, z, z, z, z, z, z, z,
                                                 z, z)
        if return_stats:
            return out, stats
        return out
    if host:
        return _host_fetch(ids, axis_name, capacity_slack, capacity,
                           cache, cache_cfg, host_admit, d, dtype, w)
    if w == 1 and cache is None:
        with _stage("owner_fetch"):
            out = table_local[jnp.clip(ids, 0, rows - 1)]
        if return_stats:
            if dedup:
                n_unique = dedup_requests(ids)[3].astype(jnp.int32)
            else:
                n_unique = jnp.int32(r)
            return out, FetchStats(jnp.int32(r), n_unique, jnp.int32(0),
                                   jnp.int32(0), jnp.int32(0))
        return out
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


def shard_rows(table: np.ndarray, n_workers: int) -> np.ndarray:
    """Pad a [N, D] host table to [W * rows, D] so it row-shards evenly."""
    n = table.shape[0]
    rows = -(-n // n_workers)
    pad = n_workers * rows - n
    if pad:
        table = np.concatenate([table, np.zeros((pad,) + table.shape[1:], table.dtype)])
    return table


def make_generator_fn(
    mesh: Mesh,
    *,
    fanouts: Tuple[int, ...] = (40, 20),
    axis_name: str = "data",
    merge_mode: str = "butterfly",
    capacity_slack: float = 2.0,
    cache_cfg: Optional[CacheConfig] = None,
    fetch_capacity: Optional[int] = None,
    feature_store: str = "device",
    feat_dim: Optional[int] = None,
    collect_stats: bool = False,
):
    """Pure generator function (no data placement — dry-run lowerable).

    ``gen_fn(device_args, seeds [W, b], rng) -> SubgraphBatch`` where
    ``device_args = (indptr [W,N+1], indices [W,E_pad], x [W*rows,D],
    y [W*rows,1])`` sharded on their leading axis.

    With ``feature_store="host"`` (requires ``feat_dim``) the feature
    table never reaches the device: ``device_args`` shrinks to
    ``(indptr, indices, y)`` and every generation returns a stacked
    ``HostMissRequest`` tail for the L3 store —
    ``gen_fn(device_args, seeds, rng) -> (batch, req)`` uncached, or
    ``gen_fn(device_args, seeds, rng, cache, admit_ids [W, S], admit_rows
    [W, S, D]) -> (batch, cache, req)`` cached, where ``admit_*`` is the
    previous step's landed gather (``host_store.empty_admit`` for the
    prologue) consumed for deferred cache admission.

    With a ``cache_cfg`` (a ``CacheConfig`` with ``n_rows > 0``) the
    generator becomes stateful-by-threading:
    ``gen_fn(device_args, seeds, rng, cache) -> (SubgraphBatch, cache)``
    where ``cache`` is a [W, ...] cache-state pytree (``FeatureCache``,
    or ``TieredCache`` in tiered mode) sharded ``P(axis_name)`` on its
    leading axis — one replica per worker in replicated mode, one
    authoritative shard per worker in sharded mode, and both at once
    (L1 replica + L2 shard) in tiered mode.
    ``fetch_capacity`` (optional) pins the per-destination owner-exchange
    capacity; the warm re-calibration hook uses it to shrink the static
    all_to_all buffers to the steady-state cache-miss count.

    With a FROZEN ``cache_cfg`` (``CacheConfig.serve_view()``) the
    generator takes the forward-only serve form:
    ``gen_fn(device_args, seeds, rng, cache) -> SubgraphBatch`` — the
    cache is a read-only input (probed, never admitted into, and not
    returned: read-mostly state has no next version to thread), which is
    what lets the serving tier hold ONE warm state and replay it across
    every request without carry plumbing.

    With ``collect_stats=True`` every signature's return grows a stacked
    ``(FetchStats, CacheStats)`` tail (leaves ``[W]``-leading, sharded
    ``P(axis_name)``) — the instrumented form the autotuner's trace
    recorder compiles.  Not available on the frozen serve form (the
    request path ships answers, not telemetry)."""
    if not fanouts:
        raise ValueError("fanouts must name at least one hop, got ()")
    if feature_store not in ("device", "host"):
        raise ValueError(f"feature_store must be 'device' or 'host', "
                         f"got {feature_store!r}")
    host = feature_store == "host"
    if host and feat_dim is None:
        raise ValueError('make_generator_fn(feature_store="host") '
                         'requires feat_dim (no device table to read it '
                         'from)')
    graph_spec = P(axis_name)
    row_spec = P(axis_name)
    repl = P()
    cached = cache_cfg is not None and cache_cfg.n_rows > 0
    frozen = cached and cache_cfg.frozen
    if frozen and host:
        raise ValueError('a frozen (read-mostly serve) cache cannot ride '
                         'the L3 staging path — build the serve generator '
                         'with feature_store="device"')
    if frozen and collect_stats:
        raise ValueError('collect_stats instruments the training-path '
                         'generator; the frozen serve form ships answers, '
                         'not telemetry — trace before serve_view()')
    if cached:
        cache_cfg = cache_cfg.validated()
        if cache_cfg.store != feature_store:
            # the generator's feature_store is authoritative — normalize
            # the cfg instead of letting the two silently disagree
            cache_cfg = cache_cfg._replace(store=feature_store)

    worker_gen = functools.partial(
        _worker_generate, fanouts=tuple(fanouts), axis_name=axis_name,
        merge_mode=merge_mode, capacity_slack=capacity_slack,
        cache_cfg=cache_cfg if cached else None,
        fetch_capacity=fetch_capacity,
        feature_store=feature_store, feat_dim=feat_dim,
        collect_stats=collect_stats)

    # the instrumented (collect_stats) form appends the per-worker
    # (FetchStats, CacheStats) pytree, restored to a [W] leading axis
    # exactly like the cache state; each wrapper/spec grows the same tail
    def _stats_tail(stats):
        return jax.tree.map(lambda a: a[None], stats)

    def _specs(*base):
        return base + ((P(axis_name),) if collect_stats else ())

    # shard_map blocks keep the sharded leading axis of size 1 per worker;
    # the wrappers drop it on the way in and restore it on the way out.
    def worker_fn(indptr, indices, xs, ys, seeds, rng):
        out = worker_gen(indptr[0], indices[0], xs, ys, seeds[0], rng)
        if collect_stats:
            batch, stats = out
            return batch, _stats_tail(stats)
        return out

    # the updated cache state's way out belongs to the insert: the TPU
    # compiler fuses its relayout with the insert's final selects
    def restore_state(cache):
        with _stage("cache_insert"):
            return restore_worker_axis(cache)

    def worker_fn_cached(indptr, indices, xs, ys, seeds, rng, cache):
        out = worker_gen(indptr[0], indices[0], xs, ys, seeds[0],
                         rng, squeeze_worker_axis(cache))
        if collect_stats:
            batch, cache, stats = out
            return batch, restore_state(cache), _stats_tail(stats)
        batch, cache = out
        return batch, restore_state(cache)

    # forward-only serve form: the frozen admit stage already returns the
    # state untouched, so there is no next cache version to ship out —
    # dropping it here removes the state round-trip from the request path
    def worker_fn_frozen(indptr, indices, xs, ys, seeds, rng, cache):
        batch, _ = worker_gen(indptr[0], indices[0], xs, ys, seeds[0],
                              rng, squeeze_worker_axis(cache))
        return batch

    # host-store variants: no device feature table; the HostMissRequest
    # comes back stacked [W, ...] (out_specs P(axis_name), leading axis
    # restored the same way as the cache state)
    def worker_fn_host(indptr, indices, ys, seeds, rng):
        out = worker_gen(indptr[0], indices[0], None, ys, seeds[0], rng)
        if collect_stats:
            batch, req, stats = out
            return (batch, jax.tree.map(lambda a: a[None], req),
                    _stats_tail(stats))
        batch, req = out
        return batch, jax.tree.map(lambda a: a[None], req)

    def worker_fn_host_cached(indptr, indices, ys, seeds, rng, cache,
                              adm_ids, adm_rows):
        out = worker_gen(
            indptr[0], indices[0], None, ys, seeds[0], rng,
            squeeze_worker_axis(cache),
            host_admit=(adm_ids[0], adm_rows[0]))
        if collect_stats:
            batch, cache, req, stats = out
            return (batch, restore_state(cache),
                    jax.tree.map(lambda a: a[None], req),
                    _stats_tail(stats))
        batch, cache, req = out
        return (batch, restore_state(cache),
                jax.tree.map(lambda a: a[None], req))

    if host and cached:
        def gen_fn(device_args, seeds, rng, cache, admit_ids, admit_rows):
            indptr, indices, ys = device_args
            return shard_map(
                worker_fn_host_cached,
                mesh=mesh,
                in_specs=(graph_spec, graph_spec, row_spec, graph_spec,
                          repl, P(axis_name), P(axis_name), P(axis_name)),
                out_specs=_specs(P(axis_name), P(axis_name), P(axis_name)),
                check_vma=False,
            )(indptr, indices, ys, seeds, rng, cache, admit_ids,
              admit_rows)
    elif host:
        def gen_fn(device_args, seeds, rng):
            indptr, indices, ys = device_args
            return shard_map(
                worker_fn_host,
                mesh=mesh,
                in_specs=(graph_spec, graph_spec, row_spec, graph_spec,
                          repl),
                out_specs=_specs(P(axis_name), P(axis_name)),
                check_vma=False,
            )(indptr, indices, ys, seeds, rng)
    elif cached and frozen:
        def gen_fn(device_args, seeds, rng, cache):
            indptr, indices, xs, ys = device_args
            return shard_map(
                worker_fn_frozen,
                mesh=mesh,
                in_specs=(graph_spec, graph_spec, row_spec, row_spec,
                          graph_spec, repl, P(axis_name)),
                out_specs=P(axis_name),
                check_vma=False,
            )(indptr, indices, xs, ys, seeds, rng, cache)
    elif cached:
        def gen_fn(device_args, seeds, rng, cache):
            indptr, indices, xs, ys = device_args
            return shard_map(
                worker_fn_cached,
                mesh=mesh,
                in_specs=(graph_spec, graph_spec, row_spec, row_spec,
                          graph_spec, repl, P(axis_name)),
                out_specs=_specs(P(axis_name), P(axis_name)),
                check_vma=False,
            )(indptr, indices, xs, ys, seeds, rng, cache)
    else:
        def gen_fn(device_args, seeds, rng):
            indptr, indices, xs, ys = device_args
            return shard_map(
                worker_fn,
                mesh=mesh,
                in_specs=(graph_spec, graph_spec, row_spec, row_spec,
                          graph_spec, repl),
                out_specs=(_specs(P(axis_name)) if collect_stats
                           else P(axis_name)),
                check_vma=False,
            )(indptr, indices, xs, ys, seeds, rng)

    return gen_fn


def make_distributed_generator(
    mesh: Mesh,
    part: PartitionedGraph,
    features: np.ndarray,
    labels: np.ndarray,
    *,
    fanouts: Tuple[int, ...] = (40, 20),
    axis_name: str = "data",
    merge_mode: str = "butterfly",
    capacity_slack: float = 2.0,
    cache_cfg: Optional[CacheConfig] = None,
    fetch_capacity: Optional[int] = None,
    feature_store: str = "device",
    host_gather_depth: int = 2,
    collect_stats: bool = False,
):
    """Build the jitted distributed generator with data placed on the mesh.

    Returns ``(gen_fn, device_args)``; every output leaf is sharded
    ``P(axis_name)`` on its leading (global-batch) axis.  With a
    ``cache_cfg`` an initial (empty) per-worker ``FeatureCache`` is
    also placed on the mesh and the return becomes
    ``(gen_fn, device_args, cache0)`` with
    ``gen_fn(device_args, seeds, rng, cache) -> (batch, cache)``.

    With ``feature_store="host"`` the feature table stays in host RAM —
    unsharded, unpadded — behind a ``HostFeatureStore`` (depth
    ``host_gather_depth``); only the CSR and labels are placed on the
    mesh and the returns become ``(gen_fn, device_args, store)`` /
    ``(gen_fn, device_args, store, cache0)`` (see ``make_generator_fn``
    for the host-mode ``gen_fn`` signature).

    ``collect_stats=True`` builds the instrumented (trace-recorder) form:
    ``gen_fn`` additionally returns a stacked per-worker
    ``(FetchStats, CacheStats)`` tail — see ``make_generator_fn``."""
    w = mesh.shape[axis_name]
    assert part.n_workers == w, (part.n_workers, w)
    host = feature_store == "host"
    y = shard_rows(labels.reshape(-1, 1).astype(np.float32), w)
    gen_fn = make_generator_fn(
        mesh, fanouts=fanouts, axis_name=axis_name, merge_mode=merge_mode,
        capacity_slack=capacity_slack, cache_cfg=cache_cfg,
        fetch_capacity=fetch_capacity, feature_store=feature_store,
        feat_dim=int(features.shape[1]) if host else None,
        collect_stats=collect_stats)
    spec = NamedSharding(mesh, P(axis_name))
    cached = cache_cfg is not None and cache_cfg.n_rows > 0
    if host:
        table = (features if features.dtype == np.float32
                 else features.astype(np.float32))
        store = HostFeatureStore(table, depth=host_gather_depth,
                                 sharding=spec)
        device_args = (
            jax.device_put(part.indptr, spec),
            jax.device_put(part.indices, spec),
            jax.device_put(y, spec),
        )
        if cached:
            cache0 = jax.device_put(
                init_cache_state(cache_cfg.validated(), table.shape[1], w),
                spec)
            return jax.jit(gen_fn), device_args, store, cache0
        return jax.jit(gen_fn), device_args, store
    x = shard_rows(features.astype(np.float32), w)
    device_args = (
        jax.device_put(part.indptr, spec),
        jax.device_put(part.indices, spec),
        jax.device_put(x, spec),
        jax.device_put(y, spec),
    )
    if cached:
        cache0 = jax.device_put(
            init_cache_state(cache_cfg.validated(), x.shape[1], w), spec)
        return jax.jit(gen_fn), device_args, cache0
    return jax.jit(gen_fn), device_args
