"""The cache insert as it was before it resolved offers per slot: a
verbatim copy of the former ``feature_cache.cache_insert`` (per-offer
way windows, two stable argsorts, a ``searchsorted`` for each set's
start, and four R-sized scatters into the state).  The exactness tests
hold the current insert to it bit for bit, alone and under ``fetch_rows``
(``fetch_states``; run as a script with a mode and a worker count, it
prints whether the two agree)."""
import contextlib
import json
import sys
from typing import Tuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import generation
from repro.core.feature_cache import (CacheConfig, FeatureCache, hash_slots,
                                      init_cache_state)


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
    scatter: the state is four arrays updated by four scatters, and
    duplicate scatter indices apply in unspecified order per scatter —
    without a pre-resolved winner, ``keys[s]`` could take id A while
    ``rows[s]`` takes B's row and every later probe of A would silently
    return B's features.
    """
    if cfg.n_rows != cache.n_rows:
        raise ValueError(f"cfg.n_rows {cfg.n_rows} != cache state rows "
                         f"{cache.n_rows}: inserting under a mismatched "
                         f"layout silently corrupts the placement")
    a, admit = cfg.assoc, cfg.admit
    c = cache.n_rows
    r = ids.shape[0]
    if r == 0:
        # empty offer batch: the rank machinery below concatenates a
        # length-1 group-start marker, which has no length-0 analogue
        return cache, jnp.int32(0)
    sets = hash_slots(ids, cfg.n_sets)
    slots = sets[:, None] * a + jnp.arange(a, dtype=jnp.int32)[None, :]
    keys_w = cache.keys[slots]                              # [R, A]
    tags_w = cache.tags[slots]
    counts_w = cache.counts[slots]
    tag_match = tags_w == ids[:, None]
    has_tag = tag_match.any(axis=-1)
    tag_way = jnp.argmax(tag_match, axis=-1).astype(jnp.int32)
    # victim policy: VIRGIN ways first (no resident AND no candidate in
    # flight — a way whose tag is mid-admission carries progress worth as
    # much as a resident's, so it scores by its counter like occupied
    # ways do), then smallest counter.  Ways claimed by a same-batch
    # TAGGED offer are excluded outright (huge score): the tagged offer
    # sits outside the preference order on its tag way, and a new
    # candidate routed onto it would trample its admission progress while
    # virgin ways sit free.
    claim_slot = sets * a + tag_way
    claimed = jnp.zeros((c,), jnp.bool_).at[
        jnp.where(jnp.logical_and(should, has_tag), claim_slot, c)
    ].set(True, mode="drop")
    victim_score = jnp.where(jnp.logical_and(keys_w < 0, tags_w < 0),
                             -1, counts_w)
    victim_score = jnp.where(claimed[slots], jnp.int32(2**30), victim_score)
    ways_pref = jnp.argsort(victim_score, axis=-1).astype(jnp.int32)  # [R, A]
    # Same-set offers within ONE batch must not all pick the same victim
    # way (the per-slot winner resolution below would then drop all but
    # one even with free ways left) — rank each NEW candidate within its
    # set and hand out ways in victim-preference order.  The rank counts
    # DISTINCT untagged ids only: duplicates of one id (several workers
    # offering the same hot row to its shard holder in one sharded
    # admission round) must share a way so the per-slot winner keeps
    # exactly one copy, and tagged offers consume no preference slot
    # (they keep their tag way).
    sets_eff = jnp.where(should, sets, cfg.n_sets)
    o1 = jnp.argsort(ids)
    order = o1[jnp.argsort(sets_eff[o1])]    # stable: (set, id) lexicographic
    s_sorted = sets_eff[order]
    i_sorted = ids[order]
    new_group = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        jnp.logical_or(s_sorted[1:] != s_sorted[:-1],
                       i_sorted[1:] != i_sorted[:-1])])
    # cumulative count of NEW-CANDIDATE group starts: constant across a
    # group (increments only at group starts), so duplicates share a rank
    nontag_start = jnp.logical_and(new_group, ~has_tag[order])
    ng = jnp.cumsum(nontag_start).astype(jnp.int32)
    set_start = jnp.searchsorted(s_sorted, s_sorted, side="left")
    before_set = ng[set_start] - nontag_start[set_start].astype(jnp.int32)
    rank = jnp.zeros((r,), jnp.int32).at[order].set(ng - before_set - 1)
    victim_way = jnp.take_along_axis(ways_pref, (rank % a)[:, None],
                                     axis=-1)[:, 0]
    way = jnp.where(has_tag, tag_way, victim_way)
    slot = sets * a + way                                   # [R]
    prev = jnp.take_along_axis(counts_w, way[:, None], axis=-1)[:, 0]
    new_count = jnp.where(has_tag, prev + 1, 1)
    # one deterministic winner per slot among the offers (max-combiner
    # scatter is order-independent); only the winner touches the slot
    idx = jnp.arange(r, dtype=jnp.int32)
    win = jnp.full((c,), -1, jnp.int32).at[
        jnp.where(should, slot, c)].max(idx, mode="drop")
    offer = jnp.logical_and(should, win[slot] == idx)
    install = jnp.logical_and(offer, new_count >= admit)
    # not-selected offers scatter OUT OF BOUNDS so mode="drop" discards them
    s_track = jnp.where(offer, slot, c)
    s_install = jnp.where(install, slot, c)
    new = FeatureCache(
        keys=cache.keys.at[s_install].set(ids, mode="drop"),
        rows=cache.rows.at[s_install].set(rows.astype(cache.rows.dtype),
                                          mode="drop"),
        tags=cache.tags.at[s_track].set(ids, mode="drop"),
        counts=cache.counts.at[s_track].set(new_count, mode="drop"),
    )
    return new, jnp.sum(install).astype(jnp.int32)


# ------------------------------------------- the two inserts under fetch_rows

def _former_insert_from(cache, ids, row_of, should, cfg):
    """The copy above in ``cache_insert_from``'s form (the W=1 fetch's
    inserts, which read only their winners' rows): it is handed every
    offer's row."""
    rows = row_of(jnp.arange(ids.shape[0], dtype=jnp.int32))
    return cache_insert(cache, ids, rows, should, cfg)


def fetch_states(mode: str, workers: int, former: bool, steps: int = 5,
                 seed: int = 0):
    """Cache states and insert counts after each of ``steps`` cached
    ``fetch_rows`` calls on the first ``workers`` devices, with
    ``generation``'s insert (``former=False``) or with the copy above
    (``former=True``).  The request stream is Zipf-duplicated within and
    across workers, so shard holders see the same id from several
    workers in one admission round."""
    rows_pw, d, r = 64, 4, 96
    cfg = CacheConfig(32, admit=2, assoc=4, mode=mode,
                      l1_rows=8 if mode == "tiered" else 0,
                      l1_promote=2).validated()
    mesh = Mesh(np.asarray(jax.devices()[:workers]), ("data",))
    spec = NamedSharding(mesh, P("data"))
    table = np.random.default_rng(seed).standard_normal(
        (workers * rows_pw, d)).astype(np.float32)

    def worker(t, i, cc):
        cc = jax.tree.map(lambda x: x[0], cc)
        out, cc, _, cs = generation.fetch_rows(t, i[0], "data", cache=cc,
                                               cache_cfg=cfg)
        return (out[None], jax.tree.map(lambda x: x[None], cc),
                cs.n_inserted[None])

    run = jax.jit(shard_map(worker, mesh=mesh,
                            in_specs=(P("data"),) * 3,
                            out_specs=(P("data"),) * 3, check_vma=False))
    state = jax.device_put(init_cache_state(cfg, d, workers), spec)
    rng = np.random.default_rng(seed + 1)
    history = []
    patch = (mock.patch.multiple(generation, cache_insert=cache_insert,
                                 cache_insert_from=_former_insert_from)
             if former else contextlib.nullcontext())
    with patch:
        for _ in range(steps):
            ids = (rng.zipf(1.3, (workers, r)) % (workers * rows_pw)
                   ).astype(np.int32)
            out, state, n_ins = run(jnp.asarray(table),
                                    jax.device_put(jnp.asarray(ids), spec),
                                    state)
            np.testing.assert_array_equal(
                np.asarray(out).reshape(workers, r, d), table[ids])
            history.append((jax.tree.map(np.asarray, state),
                            np.asarray(n_ins)))
    return history


def same_history(a, b) -> bool:
    """Whether two ``fetch_states`` histories agree leaf for leaf, bit
    for bit, and in every insert count."""
    for (sa, na), (sb, nb) in zip(a, b, strict=True):
        for x, y in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            if x.dtype != y.dtype or x.tobytes() != y.tobytes():
                return False
        if not np.array_equal(na, nb):
            return False
    return True


if __name__ == "__main__":
    _mode, _workers = sys.argv[1], int(sys.argv[2])
    print(json.dumps({"same": same_history(
        fetch_states(_mode, _workers, former=False),
        fetch_states(_mode, _workers, former=True))}))
