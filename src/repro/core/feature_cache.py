"""Device-resident hot-node feature cache (beyond-paper scaling lever).

PR 1's request-deduplicated shuffle collapses duplicate ids *within* one
iteration; on power-law graphs the same hot nodes recur *across*
iterations, so their rows cross the interconnect every step anyway.
DistDGL's locality-aware node placement and GraphScale's feature-store
caching exploit exactly this recurrence — here it becomes an explicit,
static-shape cache that sits in front of the routed ``all_to_all`` feature
shuffle (``generation.fetch_rows``):

  probe  — set-associative by multiplicative hash: node ``i`` can only
           live in set ``hash(i) mod S`` and one of its ``assoc`` ways, so
           a probe is ``assoc`` gathers + compares (no unbounded
           associative search, XLA-friendly static shapes).  ``assoc=1``
           is the direct-mapped PR 2 layout; 2/4-way sets recover the
           ~1/3 of hot ids that direct mapping loses to balls-in-bins
           slot collisions at load factor 1.
  route  — only cache *misses* enter the all_to_all; hits are served from
           the device-resident copy, bit-identical to the owner's row
           (rows are immutable node features).
  insert — frequency admission: a missed id must be seen ``admit`` times
           at its set (tracked by a candidate tag + counter, TinyLFU
           style) before it evicts a resident — one-off tail ids from
           the Zipf tail never displace hot rows.  With ``assoc > 1``
           the admission counter doubles as the victim policy: a new
           candidate lands in the way with the smallest counter (empty
           ways first), so the most-contended candidates keep their
           progress toward admission.

Three placement modes (``CacheConfig.mode``):

  "replicated" — the PR 2 behavior: every worker caches its OWN request
           stream; total distinct capacity stays ~C no matter how many
           workers join (all replicas converge on the same Zipf head).
  "sharded" — the cache id-space is partitioned across the worker axis:
           worker ``shard_of(id, W)`` is the authoritative shard for
           ``id``, so total capacity grows to W*C distinct rows.  The
           fetch front end gains a second routing stage (one all_to_all
           probe round to the shard holders) — see
           ``generation.fetch_rows``.  The shard hash uses a DIFFERENT
           multiplicative mixer than the set hash so shard routing and
           in-cache set indices stay independent (with a shared mixer,
           the ids landing on one shard would collapse onto a fraction
           of its sets).
  "tiered" — hierarchical composition of the two: a SMALL replicated L1
           (``l1_rows`` slots, direct-mapped or 2-way — the global Zipf
           head) sits in front of the sharded L2 (``n_rows`` slots per
           worker).  The L1 probe is local — a hit costs ZERO network,
           not even the shard-probe round a sharded hit pays — and only
           L1 misses enter the probe round, so the probe round's wire
           bytes shrink by the L1 hit fraction.  Rows migrate L2 -> L1
           by frequency: every row the L2 tier SERVES a worker is
           OFFERED to that worker's local L1 and installs only after
           ``l1_promote`` observations — the hottest rows therefore
           reach every worker's L1 without any broadcast, because every
           worker keeps observing them (owner-fetched rows are not
           offered: they missed both tiers, and the cold tail must not
           churn the small L1's admission tags).  The tiered state is
           the ``TieredCache`` pytree ``(l1, l2)`` of two
           ``FeatureCache``s.

Two probe-round wire formats (``CacheConfig.wire``, sharded/tiered at
W > 1): **dense** ships the full ``[W, cap, D]`` row block back from the
shard holders even though only hit slots carry data; **compact** (the
default) ships a packed hit bitmap plus a row payload bounded by
``hit_cap`` rows per destination — stage-1 bytes then scale with hits
instead of probe capacity.  The codec lives here
(``pack_hit_bitmap``/``unpack_hit_bitmap``,
``compact_hit_rows``/``expand_hit_rows``); the routing that uses it is
``generation._shard_probe``, and docs/ARCHITECTURE.md has the per-mode
byte table.

The cache is **per-worker state**: every worker keeps its own [C] keys +
[C, D] rows, threaded *functionally* through the generation step
(shard_map worker takes and returns it), the pipelined step (the carry
becomes ``(params, opt_state, batch, cache)``) and the launchers.  No
mutation, no host round-trip: the state lives in device memory across
iterations exactly like optimizer state.

Invariant the tests pin down: a cached fetch returns **bit-identical**
rows to an uncached fetch — cached rows are verbatim copies of previously
fetched table rows, and features are immutable during an epoch.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Knuth multiplicative hash constant (2^32 / phi); with a power-of-two
# set count we keep the TOP log2(S) bits of id * K, which are the
# well-mixed ones for multiplicative hashing.
_HASH_K = np.uint32(2654435761)
# murmur3 fmix multiplier for the cache-SHARD routing hash — deliberately
# a different mixer than ``_HASH_K`` so a shard's resident ids still
# spread over all of its sets (see module docstring).
_SHARD_K = np.uint32(0x85EBCA6B)

# single source of truth for the allowed policy values lives in the
# jax-free config module (ModelConfig validates against the same tuples);
# re-exported here under the names the kernels import
from .config import (VALID_CACHE_ASSOC as VALID_ASSOC,
                     VALID_CACHE_MODES as VALID_MODES,
                     VALID_CACHE_WIRES as VALID_WIRES,
                     VALID_FEATURE_STORES as VALID_STORES)


class CacheConfig(NamedTuple):
    """Static (python-int/str) cache policy knobs, safe to close over in
    jit — THE single source of cache policy, built once from
    ``ModelConfig`` (``CacheConfig.from_model``) and threaded through
    ``fetch_rows`` / ``_worker_generate`` / the launchers."""
    n_rows: int          # main-tier cache slots (the L2 in tiered mode),
                         # power of two (0 disables)
    admit: int = 2       # misses at a set before a candidate is installed
    assoc: int = 1       # ways per set (1 = direct-mapped), in VALID_ASSOC
    mode: str = "replicated"   # "replicated" | "sharded" | "tiered"
                               # (see module doc)
    l1_rows: int = 0     # tiered mode only: replicated L1 slots per
                         # worker, power of two (the global Zipf head —
                         # total device rows become l1_rows + n_rows)
    l1_promote: int = 3  # tiered mode only: observations of a row before
                         # it is promoted into this worker's L1
    wire: str = "compact"      # shard-probe response wire format,
                               # "dense" | "compact" (see module doc; only
                               # meaningful where a probe round runs —
                               # sharded/tiered modes at W > 1)
    hit_cap: int = 0     # compact wire only: per-destination row-payload
                         # slots of the probe response (0 = auto: half the
                         # probe capacity).  Hits beyond the bound are
                         # DEMOTED to misses by the shard holder — they
                         # fall through to the owner fetch, a lost hit
                         # opportunity but never a correctness loss.
    store: str = "device"      # where cache MISSES resolve: "device" pays
                               # the routed owner fetch against the
                               # device-resident table; "host" stages them
                               # for the L3 host-RAM store's async gather
                               # (core/host_store.py) — the step's output
                               # then carries a HostMissRequest and the
                               # rows land one step later
    frozen: bool = False       # read-mostly SERVE view: probes serve hits
                               # as usual but the admit stage is the
                               # identity — no admission, no L1 promotion,
                               # no tag/counter churn — so a pre-warmed
                               # cache state is bit-stable across requests
                               # and the admission collectives vanish from
                               # the request path.  Built via serve_view().

    @property
    def n_sets(self) -> int:
        """Hash sets of the main tier: ``n_rows // assoc`` (set ``s``
        owns the ``assoc`` consecutive slots starting at ``s * assoc``)."""
        return self.n_rows // self.assoc

    @property
    def l1_assoc(self) -> int:
        """L1 ways per set: direct-mapped, or 2-way when the L2 is
        set-associative (a tiny head cache gains nothing from 4 ways —
        it holds far fewer distinct ids than its set count collides)."""
        return 1 if self.assoc == 1 else 2

    def l1_config(self) -> "CacheConfig":
        """The L1 tier as a standalone replicated policy: the probe/insert
        state machine is tier-agnostic, so the L1 reuses it verbatim with
        ``l1_promote`` as the admission threshold (promotion IS frequency
        admission — a row installs after ``l1_promote`` observations)."""
        return CacheConfig(n_rows=self.l1_rows, admit=self.l1_promote,
                           assoc=self.l1_assoc, mode="replicated",
                           frozen=self.frozen)

    def l2_config(self) -> "CacheConfig":
        """The L2 tier as a standalone sharded policy (the pre-tiered
        sharded cache, unchanged); the wire format travels with it —
        the L2's probe round is the one the codec compacts."""
        return CacheConfig(n_rows=self.n_rows, admit=self.admit,
                           assoc=self.assoc, mode="sharded",
                           wire=self.wire, hit_cap=self.hit_cap,
                           store=self.store, frozen=self.frozen)

    def serve_view(self) -> "CacheConfig":
        """The read-mostly serve view of this policy: same slot layout
        (so a cache state warmed under ``self`` probes correctly), but
        ``frozen=True`` — the admit stage becomes the identity, and
        misses resolve against the device table (``store="device"``;
        serving never defers rows through the L3 staging path).  This is
        the config the serving tier compiles its bucket ladder under."""
        return self._replace(frozen=True, store="device").validated()

    def validated(self) -> "CacheConfig":
        """Self after strict cross-field validation (raises ``ValueError``
        on any inconsistent policy — e.g. a non-power-of-two tier size,
        an L1 knob outside tiered mode, or an unknown wire format).
        Call it wherever a ``CacheConfig`` is final; ``from_model``
        already does."""
        if self.n_rows <= 0:
            raise ValueError(f"cache n_rows must be > 0, got {self.n_rows}")
        if self.n_rows & (self.n_rows - 1):
            raise ValueError(
                f"cache n_rows must be a power of two, got {self.n_rows}")
        if self.assoc not in VALID_ASSOC:
            raise ValueError(
                f"cache assoc must be one of {VALID_ASSOC}, got {self.assoc}")
        if self.assoc > self.n_rows:
            raise ValueError(
                f"cache assoc {self.assoc} exceeds n_rows {self.n_rows}")
        if self.mode not in VALID_MODES:
            raise ValueError(
                f"cache mode must be one of {VALID_MODES}, got {self.mode!r}")
        if self.mode == "tiered":
            if self.l1_rows <= 0:
                raise ValueError("tiered mode requires l1_rows > 0 "
                                 f"(got {self.l1_rows})")
            if self.l1_rows & (self.l1_rows - 1):
                raise ValueError(f"l1_rows must be a power of two, "
                                 f"got {self.l1_rows}")
            if self.l1_rows > self.n_rows:
                raise ValueError(
                    f"l1_rows {self.l1_rows} exceeds the L2's n_rows "
                    f"{self.n_rows} — the L1 is the SMALL head tier")
            if self.l1_assoc > self.l1_rows:
                raise ValueError(
                    f"l1_rows {self.l1_rows} cannot hold {self.l1_assoc} ways")
            if self.l1_promote < 1:
                raise ValueError(
                    f"l1_promote must be >= 1, got {self.l1_promote}")
        elif self.l1_rows:
            raise ValueError(
                f"l1_rows is a tiered-mode knob; mode is {self.mode!r}")
        if self.wire not in VALID_WIRES:
            raise ValueError(
                f"cache wire must be one of {VALID_WIRES}, got {self.wire!r}")
        if self.hit_cap < 0:
            raise ValueError(
                f"hit_cap must be >= 0 (0 = auto), got {self.hit_cap}")
        if self.store not in VALID_STORES:
            raise ValueError(
                f"cache store must be one of {VALID_STORES}, "
                f"got {self.store!r}")
        if self.frozen and self.store != "device":
            raise ValueError(
                'a frozen (read-mostly serve) cache requires store='
                '"device" — serving resolves misses against the device '
                'table, never the L3 staging path (use serve_view())')
        return self

    @classmethod
    def from_model(cls, cfg) -> Optional["CacheConfig"]:
        """Policy from a ``ModelConfig`` (None when the cache is disabled).

        In tiered mode ``cache_l1_rows == 0`` auto-sizes the L1 to
        ``cache_rows // 8`` — the "small replicated head" default (floored
        at the L1's way count so a tiny auto-sized L1 still validates);
        outside tiered mode the L1 knobs are ignored entirely."""
        if cfg.cache_rows <= 0:
            return None
        l1 = 0
        if cfg.cache_mode == "tiered":
            l1_assoc = 1 if cfg.cache_assoc == 1 else 2
            l1 = cfg.cache_l1_rows or max(cfg.cache_rows // 8, l1_assoc)
        return cls(n_rows=cfg.cache_rows, admit=cfg.cache_admit,
                   assoc=cfg.cache_assoc, mode=cfg.cache_mode,
                   l1_rows=l1, l1_promote=cfg.cache_l1_promote,
                   wire=cfg.cache_wire,
                   hit_cap=cfg.cache_hit_cap,
                   store=cfg.feature_store).validated()


class FeatureCache(NamedTuple):
    """One worker's cache state — an explicit pytree, threaded functionally.

    The flat [C] layout is associativity-agnostic: set ``s`` owns slots
    ``s*assoc .. s*assoc + assoc - 1`` (the ``CacheConfig`` decides how the
    slots are grouped; the state arrays never change shape).

    keys    [C]     int32  resident node id per slot (-1 = empty)
    rows    [C, D]  float  resident feature rows (bit-exact table copies)
    tags    [C]     int32  candidate id awaiting admission (-1 = none)
    counts  [C]     int32  admission-progress count for the candidate
    """
    keys: jax.Array
    rows: jax.Array
    tags: jax.Array
    counts: jax.Array

    @property
    def n_rows(self) -> int:
        """Slot count ``C`` of this cache state (``keys.shape[-1]``)."""
        return self.keys.shape[-1]


class TieredCache(NamedTuple):
    """Tiered-mode per-worker state: the ``(l1, l2)`` pytree.

    ``l1`` is the small replicated head cache (``CacheConfig.l1_rows``
    slots, layout ``l1_config()``); ``l2`` is the authoritative sharded
    tier (``n_rows`` slots, layout ``l2_config()``).  Both are plain
    ``FeatureCache`` states, so every probe/insert primitive applies
    per tier unchanged."""
    l1: FeatureCache
    l2: FeatureCache


class CacheStats(NamedTuple):
    """Telemetry from one cached fetch (per-worker scalars).

    The hit population splits three ways, disjointly:

      ``n_l1_hits``    — served by the local replicated L1 (tiered mode):
                         ZERO network, not even a probe round.
      ``n_local_hits`` — served by THIS worker's main-tier cache (the
                         requester's own shard, or any hit in replicated
                         mode): no wire crossing.
      ``n_shard_hits`` — served by a REMOTE cache shard: the row crosses
                         the wire from the shard holder instead of the
                         owner (capacity multiplies by W but wire bytes
                         do not shrink).

    ``n_hits == n_l1_hits + n_local_hits + n_shard_hits``, and with
    ``n_misses`` (unique probes routed to their owner) plus ``n_l3_hits``
    (unique probes staged for the host-RAM L3 store — always 0 with the
    device-resident store) the conservation invariant
    ``n_l1_hits + n_local_hits + n_shard_hits + n_l3_hits + n_misses ==
    n_unique`` holds for every mode and both feature stores.  With
    ``store="host"`` the L3 serves every cache-tier miss that fits the
    staging capacity, so ``n_misses`` there counts only staging-overflow
    ids nobody will serve (they surface as drops too).  ``bytes_saved``
    counts only the network-free populations (L1 + local).

    The last two fields are HOLDER-side probe-round telemetry (this
    worker acting as a shard holder, not as a requester):
    ``n_probe_demoted`` counts hits the compact wire's ``hit_cap`` bound
    demoted to misses this round (they fall through to the requester's
    owner fetch — sum over workers for the global count; always 0 on the
    dense wire), and ``probe_hit_peak`` is the largest per-destination
    hit count this holder produced BEFORE demotion (max — not sum — over
    workers bounds the ``hit_cap`` a compact probe response needs; the
    hit-cap calibration reads it off a dense measurement pass)."""
    n_hits: jax.Array        # unique probes served from the cache tier
    n_misses: jax.Array      # unique probes routed to their owner
    n_inserted: jax.Array    # rows admitted into THIS worker's tiers
    bytes_saved: jax.Array   # wire bytes the network-free hits did not cross
    n_local_hits: jax.Array  # main-tier hits served without crossing the wire
    n_shard_hits: jax.Array  # hits served by a remote cache shard
    n_l1_hits: jax.Array     # hits served by the replicated L1 (no probe
                             # round either; 0 outside tiered mode)
    n_probe_demoted: jax.Array
                             # holder-side: probe hits demoted to misses
                             # by the compact wire's hit_cap bound
    probe_hit_peak: jax.Array
                             # holder-side: max per-destination probe hits
                             # before demotion (0 when no probe round ran)
    n_l3_hits: jax.Array
                             # unique probes staged for the host-RAM L3
                             # store (store="host" only, else 0; the
                             # async gather lands their rows a step later)

    @classmethod
    def zero(cls) -> "CacheStats":
        """An all-zero ``CacheStats`` (python ints — combines with either
        host-side window accumulators or device scalars)."""
        return cls(*(0,) * len(cls._fields))

    def combine(self, other: "CacheStats") -> "CacheStats":
        """Merge two windows' telemetry into one window's.

        Every counter is additive EXCEPT ``probe_hit_peak``, which is a
        per-round maximum — summing it across a window would report a
        peak no single probe round ever produced, and the hit-cap
        calibration (and the autotuner's demotion term) would then bound
        a payload that does not exist.  This is the per-window
        stat-splitting primitive: the trace recorder keeps per-step
        records and folds a window (cold half, warm half, whole run)
        with ``combine`` instead of re-measuring it."""
        vals = [a + b for a, b in zip(self[:-2], other[:-2])]
        peak = (jnp.maximum(self.probe_hit_peak, other.probe_hit_peak)
                if isinstance(self.probe_hit_peak, jax.Array)
                or isinstance(other.probe_hit_peak, jax.Array)
                else max(self.probe_hit_peak, other.probe_hit_peak))
        return CacheStats(*vals, peak, self.n_l3_hits + other.n_l3_hits)


def hash_slots(ids: jax.Array, n_sets: int) -> jax.Array:
    """Set index of each id: top bits of the multiplicative hash.

    For a direct-mapped cache (``assoc == 1``) the set IS the slot.  The
    degenerate single-set cache (``n_sets == 1``) would need a 32-bit
    logical shift — out of range for uint32 — so it short-circuits to
    set 0 for every id instead of tracing an undefined shift."""
    if n_sets <= 0 or n_sets & (n_sets - 1):
        raise ValueError(f"cache set count must be a power of two, "
                         f"got {n_sets}")
    if n_sets == 1:
        return jnp.zeros(ids.shape, jnp.int32)
    shift = 32 - (int(n_sets).bit_length() - 1)    # keep log2(n_sets) bits
    h = ids.astype(jnp.uint32) * _HASH_K
    return jax.lax.shift_right_logical(h, jnp.uint32(shift)).astype(jnp.int32)


def shard_of(ids: jax.Array, n_workers: int) -> jax.Array:
    """Cache-shard owner of each id: worker ``mix(id) mod W``.

    This is the SECOND routing function of the sharded mode — independent
    of both the row-ownership map (``id // rows``) and the in-cache set
    hash (different multiplier, see ``_SHARD_K``)."""
    if n_workers <= 1:
        return jnp.zeros(ids.shape, jnp.int32)
    h = ids.astype(jnp.uint32) * _SHARD_K
    h = jax.lax.shift_right_logical(h, jnp.uint32(16))
    return (h % np.uint32(n_workers)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Probe-round wire codec (``CacheConfig.wire == "compact"``)
#
# The dense shard-probe response ships a full [cap, D] row block per
# destination even though only the hit slots carry data.  The compact
# format ships (a) a PACKED hit bitmap — one bit per probe slot, 32 slots
# per uint32 word — and (b) a row payload holding only the hit rows, in
# slot order, bounded by ``hit_cap``.  The holder compacts (prefix-sum
# gather), the requester re-expands (prefix-sum scatter-free gather), and
# the rows are bit-identical to the dense response for every slot whose
# bit survives.  Hits beyond ``hit_cap`` are DEMOTED: the holder clears
# their bit, so the requester treats them as misses and owner-fetches —
# a lost hit opportunity, never a correctness loss (the same contract as
# probe-capacity overflow).
# ---------------------------------------------------------------------------

#: probe slots per packed bitmap word (the bitmap dtype is uint32)
WIRE_WORD_BITS = 32


def hit_bitmap_words(n_slots: int) -> int:
    """uint32 words a packed bitmap of ``n_slots`` probe slots occupies."""
    if n_slots < 0:
        raise ValueError(f"n_slots must be >= 0, got {n_slots}")
    return -(-n_slots // WIRE_WORD_BITS)


def pack_hit_bitmap(hit: jax.Array) -> jax.Array:
    """Pack a hit vector into bitmap words: [..., R] bool -> [..., W] uint32.

    Slot ``s`` maps to bit ``s % 32`` of word ``s // 32``
    (``W == hit_bitmap_words(R)``); pad bits beyond ``R`` are zero.
    Inverse of ``unpack_hit_bitmap``."""
    r = hit.shape[-1]
    words = hit_bitmap_words(r)
    pad = words * WIRE_WORD_BITS - r
    if pad:
        hit = jnp.concatenate(
            [hit, jnp.zeros(hit.shape[:-1] + (pad,), jnp.bool_)], axis=-1)
    bits = hit.reshape(hit.shape[:-1] + (words, WIRE_WORD_BITS))
    weight = jnp.left_shift(
        jnp.uint32(1), jnp.arange(WIRE_WORD_BITS, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weight, axis=-1,
                   dtype=jnp.uint32)


def unpack_hit_bitmap(words: jax.Array, n_slots: int) -> jax.Array:
    """Unpack bitmap words back to a hit vector:
    [..., W] uint32 -> [..., n_slots] bool (pad bits discarded).
    Inverse of ``pack_hit_bitmap``."""
    if hit_bitmap_words(n_slots) != words.shape[-1]:
        raise ValueError(
            f"{words.shape[-1]} bitmap words cannot encode {n_slots} slots "
            f"(expected {hit_bitmap_words(n_slots)})")
    shift = jnp.arange(WIRE_WORD_BITS, dtype=jnp.uint32)
    bits = jnp.bitwise_and(
        jnp.right_shift(words[..., :, None], shift), jnp.uint32(1))
    flat = bits.reshape(words.shape[:-1]
                        + (words.shape[-1] * WIRE_WORD_BITS,))
    return flat[..., :n_slots].astype(jnp.bool_)


def compact_hit_rows(
    hit: jax.Array, rows: jax.Array, hit_cap: int
) -> Tuple[jax.Array, jax.Array]:
    """Holder-side payload compaction (per destination).

    ``hit`` [..., R] bool, ``rows`` [..., R, D] -> ``(kept [..., R] bool,
    payload [..., hit_cap, D])``: ``kept`` marks the first ``hit_cap``
    hits per destination (later hits are demoted — their rows are NOT in
    the payload, so the bitmap shipped over the wire must be ``kept``,
    never the raw ``hit``); ``payload[..., p, :]`` is the row of the
    ``p``-th kept slot in slot order, zeros beyond the kept count.

    ``hit_cap`` is clamped to the slot count ``R`` (a payload bound wider
    than the probe block cannot ship more rows than the dense response —
    at ``hit_cap >= R`` nothing is ever demoted)."""
    if hit_cap < 0:
        raise ValueError(f"hit_cap must be >= 0, got {hit_cap}")
    hit_cap = min(hit_cap, hit.shape[-1])
    cs = jnp.cumsum(hit.astype(jnp.int32), axis=-1)        # inclusive
    kept = jnp.logical_and(hit, cs <= hit_cap)
    # slot indices of the hits, first, in slot order (stable sort keeps
    # ascending slot order inside the hit group)
    order = jnp.argsort(~hit, axis=-1, stable=True)
    sel = order[..., :hit_cap]                             # [..., hit_cap]
    n_kept = jnp.minimum(cs[..., -1:], hit_cap)            # [..., 1]
    pvalid = jnp.arange(hit_cap, dtype=jnp.int32) < n_kept
    payload = jnp.take_along_axis(rows, sel[..., None], axis=-2)
    return kept, jnp.where(pvalid[..., None], payload, 0)


def expand_hit_rows(kept: jax.Array, payload: jax.Array) -> jax.Array:
    """Requester-side payload re-expansion (per holder).

    Inverse of ``compact_hit_rows``: ``kept`` [..., R] bool (the unpacked
    wire bitmap), ``payload`` [..., hit_cap, D] -> ``rows`` [..., R, D]
    with the ``p``-th kept slot carrying ``payload[..., p, :]`` and zeros
    everywhere else — bit-identical to the dense response on kept slots."""
    hit_cap = payload.shape[-2]
    if hit_cap == 0:
        return jnp.zeros(kept.shape + (payload.shape[-1],), payload.dtype)
    pos = jnp.cumsum(kept.astype(jnp.int32), axis=-1) - 1  # exclusive rank
    idx = jnp.clip(pos, 0, hit_cap - 1)
    rows = jnp.take_along_axis(payload, idx[..., None], axis=-2)
    return jnp.where(kept[..., None], rows, 0)


def init_cache(n_rows: int, dim: int, dtype=jnp.float32) -> FeatureCache:
    """Empty single-worker cache state."""
    return FeatureCache(
        keys=jnp.full((n_rows,), -1, jnp.int32),
        rows=jnp.zeros((n_rows, dim), dtype),
        tags=jnp.full((n_rows,), -1, jnp.int32),
        counts=jnp.zeros((n_rows,), jnp.int32),
    )


def init_worker_caches(n_rows: int, dim: int, n_workers: int,
                       dtype=np.float32) -> FeatureCache:
    """Host-side [W, ...] stack of empty per-worker caches (for device_put
    with a ``P(axis)`` sharding — each worker owns one replica/shard)."""
    return FeatureCache(
        keys=np.full((n_workers, n_rows), -1, np.int32),
        rows=np.zeros((n_workers, n_rows, dim), dtype),
        tags=np.full((n_workers, n_rows), -1, np.int32),
        counts=np.zeros((n_workers, n_rows), np.int32),
    )


def cache_specs(n_rows: int, dim: int, n_workers: int = 1,
                dtype=jnp.float32) -> FeatureCache:
    """ShapeDtypeStruct stand-ins for a [W, ...] cache (dry-run input)."""
    s = jax.ShapeDtypeStruct
    return FeatureCache(
        keys=s((n_workers, n_rows), jnp.int32),
        rows=s((n_workers, n_rows, dim), dtype),
        tags=s((n_workers, n_rows), jnp.int32),
        counts=s((n_workers, n_rows), jnp.int32),
    )


def init_cache_state(cfg: CacheConfig, dim: int, n_workers: int,
                     dtype=np.float32):
    """Mode-polymorphic [W, ...] initial cache state for a ``CacheConfig``.

    THE constructor every component should use: replicated/sharded modes
    get the flat ``FeatureCache`` stack, tiered mode gets the
    ``TieredCache`` pytree ``(l1, l2)`` — callers never branch on the
    mode themselves."""
    if cfg.mode == "tiered":
        return TieredCache(
            l1=init_worker_caches(cfg.l1_rows, dim, n_workers, dtype),
            l2=init_worker_caches(cfg.n_rows, dim, n_workers, dtype))
    return init_worker_caches(cfg.n_rows, dim, n_workers, dtype)


def cache_state_specs(cfg: CacheConfig, dim: int, n_workers: int = 1,
                      dtype=jnp.float32):
    """Mode-polymorphic ShapeDtypeStruct stand-ins (dry-run input)."""
    if cfg.mode == "tiered":
        return TieredCache(
            l1=cache_specs(cfg.l1_rows, dim, n_workers, dtype),
            l2=cache_specs(cfg.n_rows, dim, n_workers, dtype))
    return cache_specs(cfg.n_rows, dim, n_workers, dtype)


#: probe implementation every cached fetch uses when the caller does not
#: pick one explicitly — "jnp" (gather+compare, the XLA path) or "pallas"
#: (the fused VMEM probe+gather kernel; native on TPU, interpreted here).
_PROBE_IMPL = "jnp"


def set_probe_impl(impl: str) -> None:
    """Select the probe implementation for cached fetches (launcher knob —
    e.g. ``train.py --cache-probe-impl pallas``).

    The setting is read at TRACE time: call it before the cached fetch is
    first jitted — already-compiled executables keep the probe they were
    traced with (the launchers set it before building any generator)."""
    global _PROBE_IMPL
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"probe impl must be 'jnp' or 'pallas', got {impl!r}")
    _PROBE_IMPL = impl


def get_probe_impl() -> str:
    """The module-level probe implementation (``"jnp"`` | ``"pallas"``)
    cached fetches trace with when the caller does not pick one
    explicitly — see ``set_probe_impl`` for the trace-time contract."""
    return _PROBE_IMPL


def cache_probe(
    cache: FeatureCache,
    ids: jax.Array,
    valid: Optional[jax.Array] = None,
    *,
    cfg: CacheConfig,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Probe [R] ids: ``(hit [R] bool, rows [R, D])`` (zeros where missed).

    ``cfg`` is REQUIRED and must be the config the state was populated
    under — the slot layout is a property of the populated state, and a
    probe under a different associativity silently misses resident rows
    (never returns wrong ones: ``keys[slot] == id`` still gates the
    gather).  ``impl`` defaults to the module setting (``set_probe_impl``);
    ``"pallas"`` routes through the fused VMEM-tiled probe+gather kernel
    (kernels/cache_gather.py, platform-dispatched via kernels/ops.py); the
    ``"jnp"`` path lowers to the same gather+compare.
    """
    if cfg.n_rows != cache.n_rows:
        raise ValueError(f"cfg.n_rows {cfg.n_rows} != cache state rows "
                         f"{cache.n_rows}: probing under a mismatched "
                         f"layout silently loses residents")
    a = cfg.assoc
    if (impl or _PROBE_IMPL) == "pallas":
        from ..kernels.ops import cache_probe_gather
        hit, rows = cache_probe_gather(cache.keys, cache.rows, ids,
                                       assoc=a, use_kernel=True)
    else:
        sets = hash_slots(ids, cfg.n_sets)
        slots = sets[:, None] * a + jnp.arange(a, dtype=jnp.int32)[None, :]
        match = cache.keys[slots] == ids[:, None]           # [R, A]
        hit = match.any(axis=-1)
        way = jnp.argmax(match, axis=-1).astype(jnp.int32)  # first match
        rows = jnp.where(hit[:, None], cache.rows[sets * a + way], 0)
    if valid is not None:
        hit = jnp.logical_and(hit, valid)
        rows = jnp.where(hit[:, None], rows, 0)
    return hit, rows


def cache_insert(
    cache: FeatureCache,
    ids: jax.Array,
    rows: jax.Array,
    should: jax.Array,
    cfg: CacheConfig,
) -> Tuple[FeatureCache, jax.Array]:
    """Offer [R] fetched rows to the cache; returns (new_cache, n_inserted).

    ``cfg`` is REQUIRED and must match the config every probe of this
    state uses (the slot layout is a property of the populated state).
    ``should`` masks the offers (missed AND actually served — a
    capacity-dropped zero row must never be cached).  Admission: a
    candidate id is installed once its counter reaches ``cfg.admit``
    (``admit <= 1`` degrades to always-insert).  Way choice inside a set:
    an id already tracked as a candidate keeps its way; a new candidate
    takes the way with the smallest admission counter, empty ways first —
    the counter IS the victim policy, so contended candidates keep their
    progress.  Distinct ids colliding on one slot within a single batch
    are resolved to ONE winner (highest request index) *before* any
    write: without a pre-resolved winner, ``keys[s]`` could take id A
    while ``rows[s]`` takes B's row and every later probe of A would
    silently return B's features.

    Offers are resolved per slot: one sort orders them by (set, id); a
    segmented scan over that order ranks each distinct new candidate
    within its set, and the rank picks its way from a per-set
    [n_sets, assoc] victim-preference table; one ``max`` scatter into [C]
    names each slot's winner (the highest request index that chose it);
    every state array is then rewritten by C-sized selects from what the
    winner offered, so nothing of R rows is written.
    """
    if cfg.n_rows != cache.n_rows:
        raise ValueError(f"cfg.n_rows {cfg.n_rows} != cache state rows "
                         f"{cache.n_rows}: inserting under a mismatched "
                         f"layout silently corrupts the placement")
    a, admit, n_sets = cfg.assoc, cfg.admit, cfg.n_sets
    c = cache.n_rows
    r = ids.shape[0]
    if r == 0:
        # empty offer batch: the group-start markers below concatenate a
        # length-1 first marker, which has no length-0 analogue
        return cache, jnp.int32(0)
    # masked offers sort into a sentinel set past the last real one.  The
    # offers of one (set, id) group share everything below but their
    # request index, and only the largest of those counts, so their order
    # within the group is free: an unstable two-key sort suffices
    sets_eff = jnp.where(should, hash_slots(ids, n_sets), n_sets)
    s_sorted, i_sorted, idx_sorted = jax.lax.sort(
        (sets_eff, ids, jnp.arange(r, dtype=jnp.int32)), num_keys=2,
        is_stable=False)
    valid = s_sorted < n_sets
    s_row = jnp.minimum(s_sorted, n_sets - 1)
    tag_match = cache.tags.reshape(n_sets, a)[s_row] == i_sorted[:, None]
    has_tag = jnp.logical_and(valid, tag_match.any(axis=-1))
    tag_way = jnp.argmax(tag_match, axis=-1).astype(jnp.int32)
    # victim policy, per set: VIRGIN ways first (no resident AND no
    # candidate in flight — a way whose tag is mid-admission scores by its
    # counter like occupied ways do), then smallest counter.  Ways claimed
    # by a same-batch TAGGED offer are excluded (huge score): a new
    # candidate routed onto the tag way would trample its admission
    # progress while virgin ways sit free.  ``claimed`` is an int32 ``max``
    # scatter: the TPU compiler sorts the R indices of a boolean ``set``
    # scatter before it applies one.
    claimed = jnp.zeros((c,), jnp.int32).at[
        jnp.where(has_tag, s_sorted * a + tag_way, c)
    ].max(1, mode="drop") > 0
    victim_score = jnp.where(
        jnp.logical_and(cache.keys < 0, cache.tags < 0), -1, cache.counts)
    victim_score = jnp.where(claimed, jnp.int32(2**30), victim_score)
    ways_pref = jnp.argsort(victim_score.reshape(n_sets, a),
                            axis=-1).astype(jnp.int32).reshape(c)
    # Same-set offers within ONE batch must not all pick the same victim
    # way (the per-slot winner resolution below would then drop all but
    # one even with free ways left) — rank each NEW candidate within its
    # set and hand out ways in victim-preference order.  The rank counts
    # DISTINCT untagged ids only: duplicates of one id (several workers
    # offering the same hot row to its shard holder in one sharded
    # admission round) must share a way so the per-slot winner keeps
    # exactly one copy, and tagged offers consume no preference slot
    # (they keep their tag way).
    first = jnp.ones((1,), jnp.bool_)
    new_set = jnp.concatenate([first, s_sorted[1:] != s_sorted[:-1]])
    new_group = jnp.logical_or(
        new_set, jnp.concatenate([first, i_sorted[1:] != i_sorted[:-1]]))
    # cumulative count of NEW-CANDIDATE group starts: constant across a
    # group (increments only at group starts), so duplicates share a rank;
    # ``ng`` never falls, so a running max carries each set's count
    # before its first offer down the set
    nontag_start = jnp.logical_and(new_group, ~has_tag)
    ng = jnp.cumsum(nontag_start, dtype=jnp.int32)
    before_set = jax.lax.cummax(
        jnp.where(new_set, ng - nontag_start.astype(jnp.int32), 0))
    rank = ng - before_set - 1
    victim_way = ways_pref[s_row * a + rank % a]
    slot = s_row * a + jnp.where(has_tag, tag_way, victim_way)
    # one deterministic winner per slot among the offers (max-combiner
    # scatter is order-independent); only the winner touches the slot
    win = jnp.full((c,), -1, jnp.int32).at[
        jnp.where(valid, slot, c)].max(idx_sorted, mode="drop")
    offer = win >= 0
    w = jnp.maximum(win, 0)
    ids_w = ids[w]
    # the winner carries a tag exactly when its tag sits in the slot it won
    new_count = jnp.where(cache.tags == ids_w, cache.counts + 1, 1)
    install = jnp.logical_and(offer, new_count >= admit)
    new = FeatureCache(
        keys=jnp.where(install, ids_w, cache.keys),
        rows=jnp.where(install[:, None], rows[w].astype(cache.rows.dtype),
                       cache.rows),
        tags=jnp.where(offer, ids_w, cache.tags),
        counts=jnp.where(offer, new_count, cache.counts),
    )
    return new, jnp.sum(install).astype(jnp.int32)


def _keys_leaf(cache) -> jax.Array:
    """The representative keys array of either state form (tiered -> L1)."""
    return (cache.l1.keys if isinstance(cache, TieredCache) else cache.keys)


def squeeze_worker_axis(cache):
    """[1, ...] shard_map block -> per-worker [...] state.

    The shape contract is explicit: the input must be a STACKED block
    whose leading worker axis has size 1 (``keys`` is [1, C]).  An
    already-squeezed state used to be accepted silently — ``a[0]`` on a
    per-worker [C] keys array returns its first SCALAR, corrupting every
    downstream probe — so both violations now raise at trace time."""
    keys = _keys_leaf(cache)
    if keys.ndim != 2:
        raise ValueError(
            f"squeeze_worker_axis expects a [1, ...] stacked block "
            f"(keys ndim 2), got keys shape {tuple(keys.shape)} — "
            f"is this state already squeezed?")
    if keys.shape[0] != 1:
        raise ValueError(
            f"squeeze_worker_axis expects the shard_map block's worker "
            f"axis of size 1, got leading axis {keys.shape[0]}")
    return jax.tree.map(lambda a: a[0], cache)


def restore_worker_axis(cache):
    """Per-worker [...] state -> [1, ...] shard_map block.

    Inverse of ``squeeze_worker_axis`` and equally strict: the input
    must be the PER-WORKER form (``keys`` is [C]); restoring an already
    stacked state would silently grow a bogus axis."""
    keys = _keys_leaf(cache)
    if keys.ndim != 1:
        raise ValueError(
            f"restore_worker_axis expects per-worker state (keys ndim 1), "
            f"got keys shape {tuple(keys.shape)} — is this state already "
            f"stacked?")
    return jax.tree.map(lambda a: a[None], cache)


def tiered_probe(
    state: TieredCache,
    ids: jax.Array,
    valid: Optional[jax.Array] = None,
    *,
    cfg: CacheConfig,
    impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Local two-tier probe: ``(l1_hit [R], l2_hit [R], rows [R, D])``.

    Both tiers of THIS worker's state are probed in one pass — the
    single-worker degenerate of tiered mode (W == 1 owns every shard) and
    the building block the fused Pallas kernel implements.  ``l1_hit``
    and ``l2_hit`` are disjoint (L1 takes priority); ``rows`` carries the
    serving tier's copy, zeros where both miss."""
    if cfg.mode != "tiered":
        raise ValueError(f"tiered_probe requires mode='tiered', "
                         f"got {cfg.mode!r}")
    if cfg.l1_rows != state.l1.n_rows or cfg.n_rows != state.l2.n_rows:
        raise ValueError(
            f"cfg tiers ({cfg.l1_rows}, {cfg.n_rows}) != state tiers "
            f"({state.l1.n_rows}, {state.l2.n_rows}): probing under a "
            f"mismatched layout silently loses residents")
    if (impl or _PROBE_IMPL) == "pallas":
        from ..kernels.ops import cache_probe_tiered
        src, rows = cache_probe_tiered(
            state.l1.keys, state.l1.rows, state.l2.keys, state.l2.rows,
            ids, l1_assoc=cfg.l1_assoc, l2_assoc=cfg.assoc, use_kernel=True)
        l1_hit = src == 1
        l2_hit = src == 2
    else:
        l1_hit, r1 = cache_probe(state.l1, ids, cfg=cfg.l1_config())
        l2_raw, r2 = cache_probe(state.l2, ids, cfg=cfg.l2_config())
        l2_hit = jnp.logical_and(l2_raw, ~l1_hit)
        rows = jnp.where(l1_hit[:, None], r1,
                         jnp.where(l2_hit[:, None], r2, 0))
    if valid is not None:
        l1_hit = jnp.logical_and(l1_hit, valid)
        l2_hit = jnp.logical_and(l2_hit, valid)
        rows = jnp.where(jnp.logical_or(l1_hit, l2_hit)[:, None], rows, 0)
    return l1_hit, l2_hit, rows
