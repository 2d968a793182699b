"""Hot-node feature cache: state machine units (direct-mapped and
set-associative), the cache-aware fetch front end (bit-identical to the
uncached path), and the Zipf wire-slot reduction the subsystem exists
for.  The sharded-mode multiworker path runs in test_distributed.py
subprocesses (forced device counts)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.feature_cache import (CacheConfig, FeatureCache, TieredCache,
                                      cache_insert, cache_probe,
                                      compact_hit_rows, expand_hit_rows,
                                      hash_slots, hit_bitmap_words,
                                      init_cache, init_cache_state,
                                      init_worker_caches, pack_hit_bitmap,
                                      restore_worker_axis, shard_of,
                                      squeeze_worker_axis, tiered_probe,
                                      unpack_hit_bitmap)
from repro.core.generation import fetch_rows


# ---------------------------------------------------------------- state units

def test_empty_cache_never_hits():
    cache = init_cache(64, 8)
    ids = jnp.arange(100, dtype=jnp.int32)
    hit, rows = cache_probe(cache, ids, cfg=CacheConfig(64))
    assert not np.asarray(hit).any()
    assert np.abs(np.asarray(rows)).max() == 0


@pytest.mark.parametrize("assoc", [1, 2, 4])
def test_insert_then_probe_roundtrips_exact_rows(assoc):
    cfg = CacheConfig(128, admit=1, assoc=assoc)
    cache = init_cache(128, 4)
    ids = jnp.asarray([3, 17, 99, 1024], jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (4, 4))
    cache, n_ins = cache_insert(cache, ids, rows, jnp.ones(4, bool), cfg)
    assert int(n_ins) == 4
    hit, got = cache_probe(cache, ids, cfg=cfg)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rows))  # bitwise
    # ids that were never inserted must miss
    hit2, _ = cache_probe(cache, jnp.asarray([5, 2048], jnp.int32), cfg=cfg)
    assert not np.asarray(hit2).any()


def test_should_mask_gates_insertion():
    """Capacity-dropped (unserved) rows must never enter the cache."""
    cfg = CacheConfig(64, admit=1)
    cache = init_cache(64, 2)
    ids = jnp.asarray([1, 2], jnp.int32)
    rows = jnp.ones((2, 2))
    cache, n_ins = cache_insert(cache, ids, rows,
                                jnp.asarray([True, False]), cfg)
    assert int(n_ins) == 1
    hit, _ = cache_probe(cache, ids, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(hit), [True, False])


def test_frequency_admission_requires_repeat_offers():
    """admit=2: one-off ids never displace anything; the second offer of the
    same id at the same set installs it."""
    cfg = CacheConfig(64, admit=2)
    cache = init_cache(64, 2)
    ids = jnp.asarray([7], jnp.int32)
    rows = jnp.full((1, 2), 3.0)
    cache, n1 = cache_insert(cache, ids, rows, jnp.ones(1, bool), cfg)
    assert int(n1) == 0                       # first offer only tracks
    hit, _ = cache_probe(cache, ids, cfg=cfg)
    assert not np.asarray(hit).any()
    cache, n2 = cache_insert(cache, ids, rows, jnp.ones(1, bool), cfg)
    assert int(n2) == 1                       # second offer installs
    hit, got = cache_probe(cache, ids, cfg=cfg)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rows))


def test_admission_counter_resets_on_different_candidate():
    """Alternating tail ids that collide on one set keep resetting each
    other's counters — the resident hot row survives."""
    c = 64
    cfg = CacheConfig(c, admit=2)
    cache = init_cache(c, 2)
    hot = jnp.asarray([5], jnp.int32)
    hot_row = jnp.full((1, 2), 1.0)
    for _ in range(2):
        cache, _ = cache_insert(cache, hot, hot_row, jnp.ones(1, bool), cfg)
    slot_of_hot = int(hash_slots(hot, c)[0])
    # find two distinct ids colliding with hot's slot
    pool = np.arange(10_000, dtype=np.int32)
    coll = pool[np.asarray(hash_slots(jnp.asarray(pool), c)) == slot_of_hot]
    coll = coll[coll != 5][:2]
    assert len(coll) == 2
    for _ in range(4):   # alternate the two colliders
        for cid in coll:
            cache, n = cache_insert(cache, jnp.asarray([cid]),
                                    jnp.zeros((1, 2)), jnp.ones(1, bool),
                                    cfg)
            assert int(n) == 0
    hit, got = cache_probe(cache, hot, cfg=cfg)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(hot_row))


def test_same_batch_slot_collision_installs_one_consistent_pair():
    """Distinct ids colliding on one direct-mapped slot within a single
    insert batch must resolve to ONE winner whose key and row agree —
    independent scatters with duplicate indices could otherwise pair id A
    with B's row and poison every later probe of A."""
    c = 64
    cfg = CacheConfig(c, admit=1)
    cache = init_cache(c, 2)
    pool = np.arange(20_000, dtype=np.int32)
    slots = np.asarray(hash_slots(jnp.asarray(pool), c))
    counts = np.bincount(slots, minlength=c)
    s = int(np.argmax(counts))
    trio = pool[slots == s][:3]
    assert len(trio) == 3
    ids = jnp.asarray(trio)
    rows = jnp.asarray(100.0 + np.arange(6, dtype=np.float32).reshape(3, 2))
    cache2, n_ins = cache_insert(cache, ids, rows, jnp.ones(3, bool), cfg)
    assert int(n_ins) == 1
    hit, got = cache_probe(cache2, ids, cfg=cfg)
    assert int(np.asarray(hit).sum()) == 1
    i = int(np.argmax(np.asarray(hit)))
    np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(rows[i]))


# ------------------------------------------------------ set-associativity

def _set_colliders(n_sets: int, target_set: int, count: int,
                   exclude=()) -> np.ndarray:
    pool = np.arange(50_000, dtype=np.int32)
    sets = np.asarray(hash_slots(jnp.asarray(pool), n_sets))
    coll = pool[sets == target_set]
    coll = coll[~np.isin(coll, list(exclude))]
    assert len(coll) >= count
    return coll[:count]


def test_two_way_set_holds_two_colliding_ids():
    """The whole point of associativity: two hot ids whose hashes collide
    both stay resident in a 2-way set (direct mapping evicts one)."""
    c, a = 64, 2
    cfg = CacheConfig(c, admit=1, assoc=a)
    pair = _set_colliders(c // a, 7, 2)
    cache = init_cache(c, 2)
    rows = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    cache, n = cache_insert(cache, jnp.asarray(pair), rows,
                            jnp.ones(2, bool), cfg)
    assert int(n) == 2       # same batch, same set -> both ways fill
    hit, got = cache_probe(cache, jnp.asarray(pair), cfg=cfg)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rows))
    # the direct-mapped layout with the same state arrays keeps only one
    cfg1 = CacheConfig(c, admit=1, assoc=1)
    d_cache = init_cache(c, 2)
    d_pair = _set_colliders(c, 7, 2)
    d_cache, n1 = cache_insert(d_cache, jnp.asarray(d_pair),
                               rows, jnp.ones(2, bool), cfg1)
    assert int(n1) == 1
    d_hit, _ = cache_probe(d_cache, jnp.asarray(d_pair), cfg=cfg1)
    assert int(np.asarray(d_hit).sum()) == 1


def test_victim_selection_evicts_smallest_admission_counter():
    """4-way victim policy: the way whose candidate counter is smallest is
    the victim — a way whose resident keeps being re-offered (large
    counter) survives a new candidate's installation."""
    c, a = 64, 4
    cfg = CacheConfig(c, admit=1, assoc=a)
    n_sets = c // a
    ids = _set_colliders(n_sets, 3, 6)
    cache = init_cache(c, 2)
    # fill all 4 ways of set 3 (one batch -> ranks spread over ways)
    first4 = jnp.asarray(ids[:4])
    rows4 = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    cache, n = cache_insert(cache, first4, rows4, jnp.ones(4, bool), cfg)
    assert int(n) == 4
    # pump one resident's counter by re-offering it as a candidate twice
    # (misses of an already-resident id cannot happen through fetch_rows,
    # so emulate contention by offering OTHER ids and re-offering one)
    keep = first4[:1]
    keep_row = rows4[:1]
    for _ in range(3):
        cache, _ = cache_insert(cache, keep, keep_row, jnp.ones(1, bool),
                                CacheConfig(c, admit=99, assoc=a))
    # now install a 5th collider: it must evict a LOW-counter way, never
    # the pumped way
    fifth = jnp.asarray(ids[4:5])
    cache, n5 = cache_insert(cache, fifth, jnp.full((1, 2), 9.0),
                             jnp.ones(1, bool), cfg)
    assert int(n5) == 1
    hit_keep, got_keep = cache_probe(cache, keep, cfg=cfg)
    assert np.asarray(hit_keep).all()
    np.testing.assert_array_equal(np.asarray(got_keep), np.asarray(keep_row))
    hit5, _ = cache_probe(cache, fifth, cfg=cfg)
    assert np.asarray(hit5).all()


def test_assoc_same_batch_set_overflow_keeps_consistent_pairs():
    """More same-set offers than ways in one batch: each installed way must
    hold a consistent (key, row) pair and the overflow is dropped."""
    c, a = 32, 2
    cfg = CacheConfig(c, admit=1, assoc=a)
    ids = _set_colliders(c // a, 5, 4)
    cache = init_cache(c, 2)
    rows = jnp.asarray(10.0 + np.arange(8, dtype=np.float32).reshape(4, 2))
    cache, n = cache_insert(cache, jnp.asarray(ids), rows,
                            jnp.ones(4, bool), cfg)
    assert int(n) == a       # one install per way, overflow dropped
    hit, got = cache_probe(cache, jnp.asarray(ids), cfg=cfg)
    assert int(np.asarray(hit).sum()) == a
    for i in np.flatnonzero(np.asarray(hit)):
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(rows[i]))


@pytest.mark.parametrize("assoc", [2, 4])
@pytest.mark.parametrize("flip", [False, True])
def test_new_candidate_spares_inflight_candidate_way(assoc, flip):
    """A way whose candidate is mid-admission carries progress: a new
    same-set candidate must take a virgin way, not trample the in-flight
    tag (which would reset its counter with free ways available) — for
    either id ordering within the batch (the rank machinery must not route
    the new candidate onto the tagged way by off-by-one)."""
    c = 8 * assoc                 # keeps n_sets small so colliders abound
    cfg = CacheConfig(c, admit=2, assoc=assoc)
    ids = _set_colliders(c // assoc, 2, 2)
    x, y = int(ids[0]), int(ids[1])
    if flip:
        x, y = y, x
    cache = init_cache(c, 2)
    # offer X once: tagged somewhere, count 1, nothing installed
    cache, n0 = cache_insert(cache, jnp.asarray([x], jnp.int32),
                             jnp.ones((1, 2)), jnp.ones(1, bool), cfg)
    assert int(n0) == 0
    # offer X and Y together: X's second offer must install (progress
    # kept), Y must track in a DIFFERENT way
    batch = jnp.asarray([x, y], jnp.int32)
    cache, n1 = cache_insert(cache, batch, jnp.ones((2, 2)),
                             jnp.ones(2, bool), cfg)
    assert int(n1) == 1
    hit, _ = cache_probe(cache, jnp.asarray([x], jnp.int32), cfg=cfg)
    assert np.asarray(hit).all()
    assert int(np.asarray(cache.tags == y).sum()) == 1   # Y tracked too
    # Y's second offer now installs alongside X
    cache, n2 = cache_insert(cache, jnp.asarray([y], jnp.int32),
                             jnp.ones((1, 2)), jnp.ones(1, bool), cfg)
    assert int(n2) == 1
    hit2, _ = cache_probe(cache, batch, cfg=cfg)
    assert np.asarray(hit2).all()


def test_duplicate_id_offers_occupy_one_way():
    """Sharded admission hands the shard holder the SAME id from several
    source workers in one batch — it must land in exactly one way (and
    count one admission step), never clone itself across the set or evict
    unrelated residents from every way."""
    c, a = 32, 4
    cfg = CacheConfig(c, admit=1, assoc=a)
    cache = init_cache(c, 2)
    ids = jnp.asarray([77, 77, 77, 77], jnp.int32)   # 4 workers, same id
    rows = jnp.full((4, 2), 5.0)
    cache, n = cache_insert(cache, ids, rows, jnp.ones(4, bool), cfg)
    assert int(n) == 1
    assert int(np.asarray(cache.keys == 77).sum()) == 1
    hit, got = cache_probe(cache, ids[:1], cfg=cfg)
    assert np.asarray(hit).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rows[:1]))
    # duplicates + a distinct collider in one batch: the collider still
    # gets its own way
    sets = hash_slots(jnp.arange(50_000, dtype=jnp.int32), c // a)
    coll = np.arange(50_000)[np.asarray(sets)
                             == int(hash_slots(ids[:1], c // a)[0])]
    coll = coll[coll != 77][:1]
    batch = jnp.asarray([77, int(coll[0]), 77], jnp.int32)
    cache2, n2 = cache_insert(init_cache(c, 2), batch,
                              jnp.ones((3, 2)), jnp.ones(3, bool), cfg)
    assert int(n2) == 2
    hit2, _ = cache_probe(cache2, batch, cfg=cfg)
    assert np.asarray(hit2).all()
    # admit=2: duplicate offers in ONE batch are one tracking step, so the
    # candidate is not yet installed
    cfg2 = CacheConfig(c, admit=2, assoc=a)
    cache3, n3 = cache_insert(init_cache(c, 2), ids, rows,
                              jnp.ones(4, bool), cfg2)
    assert int(n3) == 0
    assert int(np.asarray(cache3.tags == 77).sum()) == 1


# ------------------------------------ exactness against the former insert

EXACT_C = 64


def _full_state(cfg, d, rng):
    """A cache whose every slot holds a resident and a candidate, each an
    id of the slot's own set, with counts short of admission or past it."""
    c, a = cfg.n_rows, cfg.assoc
    pool = np.arange(64 * c, dtype=np.int32)
    sets = np.asarray(hash_slots(jnp.asarray(pool), cfg.n_sets))
    keys, tags = np.empty(c, np.int32), np.empty(c, np.int32)
    for s in range(cfg.n_sets):
        mine = pool[sets == s]
        keys[s * a:(s + 1) * a] = mine[:a]
        tags[s * a:(s + 1) * a] = mine[a:2 * a]
    return FeatureCache(
        keys=jnp.asarray(keys),
        rows=jnp.asarray(rng.standard_normal((c, d)).astype(np.float32)),
        tags=jnp.asarray(tags),
        counts=jnp.asarray(rng.integers(0, cfg.admit + 2, c, np.int32)))


def _offer_stream(kind, r, cfg, state, rng):
    """``(ids, should)`` of one offer batch of length ``r``."""
    c = cfg.n_rows
    zipf = (rng.zipf(1.3, r) % (4 * c)).astype(np.int32)
    if kind == "zipf":
        return zipf, rng.random(r) < 0.8
    if kind == "same_set":
        # many distinct ids (and repeats) on two sets: more new
        # candidates than ways
        pool = np.arange(64 * c, dtype=np.int32)
        sets = np.asarray(hash_slots(jnp.asarray(pool), cfg.n_sets))
        mine = pool[sets < 2][:8 * cfg.assoc]
        return rng.choice(mine, r).astype(np.int32), np.ones(r, bool)
    if kind == "masked":
        return zipf, np.zeros(r, bool)
    # candidates in flight offered again, among new ids
    tags = np.asarray(state.tags)
    tags = tags[tags >= 0]
    if tags.size:
        zipf = np.where(rng.random(r) < 0.6, rng.choice(tags, r), zipf)
    return zipf.astype(np.int32), rng.random(r) < 0.9


@pytest.mark.parametrize("r", [0, 1, EXACT_C // 2, 8 * EXACT_C])
@pytest.mark.parametrize("admit", [1, 2, 3])
@pytest.mark.parametrize("assoc", [1, 2, 4])
def test_insert_matches_former_insert_bit_for_bit(assoc, admit, r):
    """The per-slot insert leaves the state the former per-offer insert
    (``tests/_cache_insert_ref.py``) leaves, leaf for leaf and bit for
    bit, with the same insert count, over multi-step sequences from an
    empty and from a full cache: Zipf-duplicated ids, many ids of one
    set, all offers masked, and candidates in flight offered again."""
    from _cache_insert_ref import cache_insert as former_insert

    cfg = CacheConfig(EXACT_C, admit=admit, assoc=assoc)
    new_fn = jax.jit(lambda s, i, x, m: cache_insert(s, i, x, m, cfg))
    old_fn = jax.jit(lambda s, i, x, m: former_insert(s, i, x, m, cfg))
    rng = np.random.default_rng(100 * assoc + 10 * admit + r)
    d = 3
    for start in (init_cache(EXACT_C, d), _full_state(cfg, d, rng)):
        new = old = start
        for step, kind in enumerate(("zipf", "same_set", "masked",
                                     "tagged", "zipf", "tagged")):
            ids, should = _offer_stream(kind, r, cfg, new, rng)
            rows = rng.standard_normal((r, d)).astype(np.float32)
            new, n_new = new_fn(new, ids, rows, should)
            old, n_old = old_fn(old, ids, rows, should)
            for name, x, y in zip(FeatureCache._fields, new, old):
                x, y = np.asarray(x), np.asarray(y)
                assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), \
                    (kind, step, name)
            assert int(n_new) == int(n_old), (kind, step)


@pytest.mark.parametrize("mode", ["tiered", "sharded"])
def test_fetch_states_match_former_insert(mode):
    """Under ``fetch_rows`` at W=1 (the tiered tier's L2 admission and L1
    promotion), the caches evolve as they did with the former insert."""
    import _cache_insert_ref

    assert _cache_insert_ref.same_history(
        _cache_insert_ref.fetch_states(mode, 1, former=False),
        _cache_insert_ref.fetch_states(mode, 1, former=True))


def test_fetch_states_match_former_insert_on_four_workers():
    """The same for the sharded tier on four virtual CPU devices, where
    shard holders admit W x cap offers with the same id from several
    workers."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    tests = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tests.parent / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, str(tests / "_cache_insert_ref.py"), "sharded", "4"],
        capture_output=True, text=True, timeout=600, env=env, cwd=tests)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"same": True}


# ------------------------------------------------------------- hash guards

def test_hash_slots_rejects_non_power_of_two():
    with pytest.raises(ValueError):
        hash_slots(jnp.arange(4, dtype=jnp.int32), 100)


def test_hash_slots_degenerate_single_set():
    """n_sets == 1 would need a 32-bit shift (out of range on uint32) —
    the guard maps every id to set 0 instead of tracing UB."""
    slots = hash_slots(jnp.asarray([0, 1, 7, 2**30], jnp.int32), 1)
    np.testing.assert_array_equal(np.asarray(slots), 0)
    # a 1-row cache is usable end to end
    cfg = CacheConfig(1, admit=1)
    cache = init_cache(1, 2)
    cache, n = cache_insert(cache, jnp.asarray([42], jnp.int32),
                            jnp.ones((1, 2)), jnp.ones(1, bool), cfg)
    assert int(n) == 1
    hit, _ = cache_probe(cache, jnp.asarray([42], jnp.int32), cfg=cfg)
    assert np.asarray(hit).all()


def test_shard_of_is_balanced_and_differs_from_set_hash():
    """The shard router must spread ids over workers AND stay independent
    of the set hash — a shared mixer would collapse one shard's residents
    onto a fraction of its sets."""
    ids = jnp.arange(20_000, dtype=jnp.int32)
    for w in (2, 4, 7, 8):
        s = np.asarray(shard_of(ids, w))
        counts = np.bincount(s, minlength=w)
        assert counts.min() > 0.8 * len(ids) / w, (w, counts)
    # within one shard, the set indices still cover most sets
    n_sets = 64
    shard0 = np.asarray(ids)[np.asarray(shard_of(ids, 8)) == 0]
    sets = np.asarray(hash_slots(jnp.asarray(shard0), n_sets))
    assert len(np.unique(sets)) == n_sets


def test_probe_and_insert_reject_mismatched_layout():
    """The cfg must describe the POPULATED state: a different n_rows would
    silently probe/insert at wrong slots, so it raises instead."""
    cache = init_cache(64, 2)
    ids = jnp.asarray([1], jnp.int32)
    with pytest.raises(ValueError):
        cache_probe(cache, ids, cfg=CacheConfig(32))
    with pytest.raises(ValueError):
        cache_insert(cache, ids, jnp.ones((1, 2)), jnp.ones(1, bool),
                     CacheConfig(128))


def test_cache_config_validation():
    with pytest.raises(ValueError):
        CacheConfig(100).validated()            # not a power of two
    with pytest.raises(ValueError):
        CacheConfig(64, assoc=3).validated()    # unsupported ways
    with pytest.raises(ValueError):
        CacheConfig(64, mode="global").validated()
    assert CacheConfig(64, assoc=4, mode="sharded").validated().n_sets == 16


def test_model_config_rounds_cache_rows():
    """cache_rows validation happens at CONSTRUCTION, not trace time."""
    from repro.core.config import ModelConfig
    cfg = ModelConfig(name="t", family="gcn", cache_rows=1000)
    assert cfg.cache_rows == 1024
    cfg2 = ModelConfig(name="t", family="gcn", cache_rows=4096)
    assert cfg2.cache_rows == 4096
    with pytest.raises(ValueError):
        ModelConfig(name="t", family="gcn", cache_rows=-1)
    with pytest.raises(ValueError):
        ModelConfig(name="t", family="gcn", cache_assoc=3)
    with pytest.raises(ValueError):
        ModelConfig(name="t", family="gcn", cache_mode="bogus")
    c3 = CacheConfig.from_model(
        ModelConfig(name="t", family="gcn", cache_rows=512, cache_admit=3,
                    cache_assoc=2, cache_mode="sharded"))
    assert c3 == CacheConfig(512, 3, 2, "sharded")
    assert CacheConfig.from_model(
        ModelConfig(name="t", family="gcn", cache_rows=0)) is None


def test_worker_axis_roundtrip():
    stacked = init_worker_caches(32, 4, n_workers=1)
    c = squeeze_worker_axis(jax.tree.map(jnp.asarray, FeatureCache(*stacked)))
    assert c.keys.shape == (32,)
    r = restore_worker_axis(c)
    assert r.keys.shape == (1, 32) and r.rows.shape == (1, 32, 4)


def test_worker_axis_shape_contract_is_explicit():
    """Regression for the silent-acceptance bug: squeezing an
    already-squeezed cache used to index keys[0] — a SCALAR — and corrupt
    every downstream probe; restoring an already-stacked cache grew a
    bogus axis.  Both now raise, for the flat AND the tiered state."""
    stacked = jax.tree.map(jnp.asarray, init_worker_caches(32, 4, 1))
    per_worker = squeeze_worker_axis(stacked)
    with pytest.raises(ValueError, match="already squeezed"):
        squeeze_worker_axis(per_worker)
    with pytest.raises(ValueError, match="already\\s+stacked"):
        restore_worker_axis(stacked)
    # roundtrip identity both ways
    rt = squeeze_worker_axis(restore_worker_axis(per_worker))
    for a, b in zip(rt, per_worker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the worker axis must be the size-1 shard_map block, not a [W>1] stack
    with pytest.raises(ValueError, match="size 1"):
        squeeze_worker_axis(jax.tree.map(jnp.asarray,
                                         init_worker_caches(32, 4, 4)))
    # tiered state: same contract through the (l1, l2) pytree
    tcfg = CacheConfig(32, assoc=2, mode="tiered", l1_rows=8).validated()
    tstacked = jax.tree.map(jnp.asarray, init_cache_state(tcfg, 4, 1))
    tper = squeeze_worker_axis(tstacked)
    assert tper.l1.keys.shape == (8,) and tper.l2.keys.shape == (32,)
    with pytest.raises(ValueError, match="already squeezed"):
        squeeze_worker_axis(tper)
    with pytest.raises(ValueError, match="already\\s+stacked"):
        restore_worker_axis(tstacked)
    assert restore_worker_axis(tper).l1.keys.shape == (1, 8)


# ------------------------------------------------------------- tiered tier

def test_tiered_config_validation_and_tier_views():
    with pytest.raises(ValueError):
        CacheConfig(64, mode="tiered").validated()          # no L1
    with pytest.raises(ValueError):
        CacheConfig(64, mode="tiered", l1_rows=12).validated()  # not pow2
    with pytest.raises(ValueError):
        CacheConfig(64, mode="sharded", l1_rows=8).validated()  # wrong mode
    with pytest.raises(ValueError):
        CacheConfig(64, mode="tiered", l1_rows=8,
                    l1_promote=0).validated()
    cfg = CacheConfig(64, admit=2, assoc=4, mode="tiered", l1_rows=8,
                      l1_promote=3).validated()
    # tier views: L1 is a standalone replicated policy with the promotion
    # threshold as its admission knob and capped 2-way sets; L2 is the
    # pre-tiered sharded policy unchanged
    assert cfg.l1_assoc == 2
    assert cfg.l1_config() == CacheConfig(8, admit=3, assoc=2,
                                          mode="replicated")
    assert cfg.l2_config() == CacheConfig(64, admit=2, assoc=4,
                                          mode="sharded")
    assert CacheConfig(64, assoc=1, mode="tiered",
                       l1_rows=8).validated().l1_assoc == 1


def test_tiered_from_model_auto_sizes_l1():
    from repro.core.config import ModelConfig
    cfg = CacheConfig.from_model(ModelConfig(
        name="t", family="gcn", cache_rows=4096, cache_mode="tiered"))
    assert cfg.mode == "tiered" and cfg.l1_rows == 4096 // 8
    cfg2 = CacheConfig.from_model(ModelConfig(
        name="t", family="gcn", cache_rows=4096, cache_mode="tiered",
        cache_l1_rows=1000, cache_l1_promote=2))
    assert cfg2.l1_rows == 1024 and cfg2.l1_promote == 2   # rounded up
    # the auto floor respects the L1's way count: a tiny set-associative
    # tiered cache must still produce a VALID config
    tiny = CacheConfig.from_model(ModelConfig(
        name="t", family="gcn", cache_rows=8, cache_mode="tiered",
        cache_assoc=2))
    assert tiny.l1_rows == 2 and tiny.l1_assoc == 2
    # non-tiered modes IGNORE leftover L1 knobs instead of raising — the
    # launchers override cache_mode field-by-field on tiered arch configs
    # (e.g. --cache-mode sharded on graphgen-gcn-deep), so a cross-field
    # check at ModelConfig construction would break every such override
    sharded = CacheConfig.from_model(ModelConfig(
        name="t", family="gcn", cache_rows=64, cache_mode="sharded",
        cache_l1_rows=8))
    assert sharded.mode == "sharded" and sharded.l1_rows == 0
    with pytest.raises(ValueError):
        ModelConfig(name="t", family="gcn", cache_l1_promote=0)
    with pytest.raises(ValueError):
        ModelConfig(name="t", family="gcn", cache_l1_rows=-2)


def test_tiered_probe_l1_priority_and_bit_identity():
    """The fused local probe: an id resident in BOTH tiers is reported as
    an L1 hit (the cheaper tier wins), rows are verbatim copies from the
    serving tier, and the jnp and pallas paths agree bit-for-bit."""
    cfg = CacheConfig(32, admit=1, assoc=2, mode="tiered", l1_rows=8,
                      l1_promote=1).validated()
    state = TieredCache(l1=init_cache(8, 2), l2=init_cache(32, 2))
    both = jnp.asarray([3], jnp.int32)
    l2_only = jnp.asarray([100], jnp.int32)
    row_a, row_b = jnp.full((1, 2), 1.0), jnp.full((1, 2), 2.0)
    l1, _ = cache_insert(state.l1, both, row_a, jnp.ones(1, bool),
                         cfg.l1_config())
    l2, _ = cache_insert(state.l2, both, row_a, jnp.ones(1, bool),
                         cfg.l2_config())
    l2, _ = cache_insert(l2, l2_only, row_b, jnp.ones(1, bool),
                         cfg.l2_config())
    state = TieredCache(l1=l1, l2=l2)
    ids = jnp.asarray([3, 100, 999], jnp.int32)
    l1_hit, l2_hit, rows = tiered_probe(state, ids, cfg=cfg)
    np.testing.assert_array_equal(np.asarray(l1_hit), [True, False, False])
    np.testing.assert_array_equal(np.asarray(l2_hit), [False, True, False])
    np.testing.assert_array_equal(np.asarray(rows),
                                  np.asarray([[1., 1.], [2., 2.], [0., 0.]]))
    p1, p2, pr = tiered_probe(state, ids, cfg=cfg, impl="pallas")
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(l1_hit))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(l2_hit))
    np.testing.assert_array_equal(np.asarray(pr), np.asarray(rows))
    # layout mismatch rejected, like the flat probe
    with pytest.raises(ValueError):
        tiered_probe(state, ids,
                     cfg=CacheConfig(32, mode="tiered", l1_rows=16))
    with pytest.raises(ValueError):
        tiered_probe(state, ids, cfg=CacheConfig(32))   # not tiered


def test_l1_promotion_requires_repeat_observations():
    """The L2 -> L1 migration gate: with l1_promote=2, one observation of
    an L2-served row only tracks it in the L1; the second installs it —
    after which the id is served with zero network (an L1 hit)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    cfg = CacheConfig(64, admit=1, assoc=2, mode="tiered", l1_rows=16,
                      l1_promote=2).validated()
    n, d = 40, 3
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    mesh = make_local_mesh(1, 1)

    def worker(t, i, c):
        out, c, fs, cs = fetch_rows(t, i, "data",
                                    cache=squeeze_worker_axis(c),
                                    cache_cfg=cfg)
        return (out, restore_worker_axis(c),
                jax.tree.map(lambda a: a[None], (fs, cs)))

    run = jax.jit(shard_map(
        worker, mesh=mesh, in_specs=(P(), P(), P("data")),
        out_specs=(P(), P("data"), P("data")), check_vma=False))
    state = jax.tree.map(jnp.asarray, init_cache_state(cfg, d, 1))
    ids = jnp.asarray(np.arange(10, dtype=np.int32))
    l1_hits = []
    for it in range(4):
        out, state, (fs, cs) = run(table, ids, state)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(table)[:10])
        l1_hits.append(int(cs.n_l1_hits[0]))
    # it0: owner fetch (L2 admission).  it1: L2 serves -> first L1
    # observation, only tracked.  it2: probe still misses (the second
    # observation installs AFTER it2's probe).  it3: the L1 now serves
    # the stream network-free.
    assert l1_hits[0] == l1_hits[1] == l1_hits[2] == 0, l1_hits
    assert l1_hits[3] > 0, l1_hits


# --------------------------------------------------- conservation invariant

@pytest.mark.parametrize("mode", ["none", "replicated", "sharded", "tiered"])
def test_hit_conservation_invariant_adversarial_streams(mode):
    """For EVERY cache mode, ``n_l1_hits + n_local_hits + n_shard_hits +
    n_misses == n_distinct`` on each fetch — including the adversarial
    stream shapes where counter bookkeeping slips: all-duplicate,
    all-distinct, single-id, and the empty batch.  Each stream runs cold
    AND warm (the warm pass moves population between the categories; the
    sum must not move), and rows stay bit-identical throughout."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    n, d = 64, 3
    table = jnp.asarray(
        np.arange(n * d, dtype=np.float32).reshape(n, d))
    mesh = make_local_mesh(1, 1)
    cfg = None if mode == "none" else CacheConfig(
        16, admit=1, assoc=2, mode=mode,
        l1_rows=8 if mode == "tiered" else 0, l1_promote=1).validated()
    if cfg is None:
        run = jax.jit(shard_map(
            lambda t, i: fetch_rows(t, i, "data", return_stats=True),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))
        state = None
    else:
        def worker(t, i, c):
            out, c, fs, cs = fetch_rows(t, i, "data",
                                        cache=squeeze_worker_axis(c),
                                        cache_cfg=cfg)
            return (out, restore_worker_axis(c),
                    jax.tree.map(lambda a: a[None], (fs, cs)))

        run = jax.jit(shard_map(
            worker, mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=(P(), P("data"), P("data")), check_vma=False))
        state = jax.tree.map(jnp.asarray, init_cache_state(cfg, d, 1))
    streams = [
        np.full(64, 7, np.int32),          # all-duplicate
        np.arange(48, dtype=np.int32),     # all-distinct
        np.asarray([5], np.int32),         # single id
        np.zeros(0, np.int32),             # empty batch
    ]
    for ids_np in streams:
        distinct = len(np.unique(ids_np))
        for _ in range(2):                 # cold pass, then warm pass
            ids = jnp.asarray(ids_np)
            if cfg is None:
                out, fs = run(table, ids)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(table)[ids_np])
                # no cache tier: everything distinct is a "miss"
                assert int(fs.n_unique) == distinct
                continue
            out, state, (fs, cs) = run(table, ids, state)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(table)[ids_np])
            l1 = int(cs.n_l1_hits[0])
            loc = int(cs.n_local_hits[0])
            sh = int(cs.n_shard_hits[0])
            ms = int(cs.n_misses[0])
            assert l1 + loc + sh + ms == distinct, (
                mode, ids_np.shape, l1, loc, sh, ms, distinct)
            assert int(cs.n_hits[0]) == l1 + loc + sh
            assert l1 >= 0 and loc >= 0 and sh >= 0 and ms >= 0
            if mode != "tiered":
                assert l1 == 0
            # single worker owns every shard: nothing is remote
            assert sh == 0


# ------------------------------------------------- cache-aware fetch_rows

_FETCH_FNS = {}


def _fetch_fn(kind, admit=1, assoc=1, dedup=True):
    """Jitted single-worker fetch wrappers, cached so the hypothesis sweep
    and the 20-iteration Zipf run compile once per shape."""
    key = (kind, admit, assoc, dedup)
    if key in _FETCH_FNS:
        return _FETCH_FNS[key]
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(1, 1)
    if kind == "plain":
        fn = jax.jit(shard_map(
            lambda t, i: fetch_rows(t, i, "data", dedup=dedup),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False))
    else:
        def worker(t, i, c):
            cfg = CacheConfig(
                squeeze_worker_axis(c).n_rows, admit=admit, assoc=assoc)
            out, c, fs, cs = fetch_rows(t, i, "data",
                                        cache=squeeze_worker_axis(c),
                                        cache_cfg=cfg)
            return (out, restore_worker_axis(c),
                    jax.tree.map(lambda a: a[None], (fs, cs)))

        fn = jax.jit(shard_map(
            worker, mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=(P(), P("data"), P("data")), check_vma=False))
    _FETCH_FNS[key] = fn
    return fn


def _run_fetch(table, ids, *, cache=None, admit=1, assoc=1, dedup=True):
    if cache is None:
        return _fetch_fn("plain", dedup=dedup)(table, ids)
    return _fetch_fn("cached", admit=admit, assoc=assoc)(table, ids, cache)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_cached_fetch_bit_identical_to_uncached(seed):
    """THE cache contract: across several iterations of a duplicated,
    recurring request stream, the cached path returns bit-identical rows to
    the uncached path (and to the table itself)."""
    rng = np.random.default_rng(seed)
    n, d = 40, 5
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    cache = jax.tree.map(jnp.asarray, init_worker_caches(16, d, 1))
    for _ in range(4):
        ids = jnp.asarray(rng.integers(0, n, 50, dtype=np.int32))
        want = _run_fetch(table, ids)
        got, cache, (fs, cs) = _run_fetch(table, ids, cache=cache, admit=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table)[np.asarray(ids)])
        assert int(fs.n_dropped[0]) == 0


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([2, 4]))
def test_cached_fetch_bit_identical_set_associative(seed, assoc):
    """The bit-identity contract holds for every associativity."""
    rng = np.random.default_rng(seed)
    n, d = 48, 3
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    cache = jax.tree.map(jnp.asarray, init_worker_caches(16, d, 1))
    for _ in range(3):
        ids = jnp.asarray(rng.integers(0, n, 40, dtype=np.int32))
        got, cache, (fs, cs) = _run_fetch(table, ids, cache=cache,
                                          admit=1, assoc=assoc)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table)[np.asarray(ids)])
        assert int(fs.n_dropped[0]) == 0


def test_cached_fetch_hits_accumulate_and_route_count_drops():
    """Second identical request stream: hits appear, routed uniques fall,
    and n_requests/n_unique telemetry stays consistent."""
    rng = np.random.default_rng(0)
    n, d = 64, 3
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, n, 128, dtype=np.int32))
    n_uniq = len(np.unique(np.asarray(ids)))
    cache = jax.tree.map(jnp.asarray, init_worker_caches(256, d, 1))
    _, cache, (fs1, cs1) = _run_fetch(table, ids, cache=cache, admit=1)
    assert int(cs1.n_hits[0]) == 0
    assert int(fs1.n_unique[0]) == int(cs1.n_misses[0]) == n_uniq
    assert int(cs1.n_inserted[0]) == n_uniq
    got, cache, (fs2, cs2) = _run_fetch(table, ids, cache=cache, admit=1)
    assert int(cs2.n_hits[0]) > 0
    assert int(fs2.n_unique[0]) == n_uniq - int(cs2.n_hits[0])
    # replicated mode: every hit is local, bytes_saved counts all of them
    assert int(cs2.n_local_hits[0]) == int(cs2.n_hits[0])
    assert int(cs2.n_shard_hits[0]) == 0
    assert int(cs2.bytes_saved[0]) == int(cs2.n_hits[0]) * d * 4
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table)[np.asarray(ids)])


def test_cache_requires_dedup():
    table = jnp.zeros((8, 2))
    cache = init_cache(8, 2)
    with pytest.raises(ValueError):
        # graphlint: disable=cacheconfig-required  # asserting this exact rejection path
        fetch_rows(table, jnp.zeros(4, jnp.int32), "data", dedup=False,
                   cache=cache)


def test_cache_requires_cfg():
    """A cache state without its policy object must be rejected — probing
    an assoc>1/sharded state under a guessed default layout would silently
    lose the residents instead of erroring."""
    table = jnp.zeros((8, 2))
    cache = init_cache(8, 2)
    with pytest.raises(ValueError):
        # graphlint: disable=cacheconfig-required  # the missing cfg IS what this test asserts
        fetch_rows(table, jnp.zeros(4, jnp.int32), "data", cache=cache)


_PALLAS_FETCH = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core.feature_cache import (CacheConfig, init_cache_state,
                                      set_probe_impl)
from repro.core.generation import fetch_rows
from repro.kernels import ops

calls = []
kernel = ops.cache_probe_gather


def counted(*a, **k):
    calls.append(1)
    return kernel(*a, **k)


ops.cache_probe_gather = counted
w, rows_pw, d, r = 2, 32, 8, 96
cfg = CacheConfig(32, admit=1, assoc=2).validated()
rng = np.random.default_rng(2)
table = rng.standard_normal((w * rows_pw, d)).astype(np.float32)
ids = rng.integers(0, w * rows_pw, (w, r)).astype(np.int32)
mesh = Mesh(np.asarray(jax.devices()[:w]), ("data",))


def worker(t, i, c):
    c = jax.tree.map(lambda x: x[0], c)
    out, c, _, cs = fetch_rows(t, i[0], "data", cache=c, cache_cfg=cfg)
    return out[None], jax.tree.map(lambda x: x[None], c), cs.n_hits[None]


set_probe_impl("pallas")
run = jax.jit(shard_map(worker, mesh=mesh, in_specs=(P("data"),) * 3,
                        out_specs=(P("data"),) * 3, check_vma=False))
cache = jax.device_put(init_cache_state(cfg, d, w),
                       NamedSharding(mesh, P("data")))
_, cache, _ = run(jnp.asarray(table), jnp.asarray(ids), cache)
got, _, hits = run(jnp.asarray(table), jnp.asarray(ids), cache)
print(json.dumps({"same": bool(np.array_equal(np.asarray(got), table[ids])),
                  "hits": int(np.asarray(hits).sum()),
                  "kernel_calls": len(calls)}))
"""


def test_pallas_probe_impl_serves_cached_fetch():
    """set_probe_impl('pallas') routes the probe of the routed cached
    fetch (two virtual CPU devices, in a subprocess) through the fused
    kernel — rows stay bit-identical to the table."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from repro.core.feature_cache import set_probe_impl

    src = Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join([str(src), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _PALLAS_FETCH],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["same"] and got["hits"] > 0 and got["kernel_calls"] > 0, got
    with pytest.raises(ValueError):
        set_probe_impl("cuda")


# ------------------------------------------------- probe-round wire codec

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_bitmap_pack_unpack_roundtrip(seed):
    """Property: pack then unpack reproduces ANY hit vector exactly, for
    slot counts on and off the 32-bit word boundary, and the packed form
    occupies exactly ceil(R/32) uint32 words."""
    rng = np.random.default_rng(seed)
    r = int(rng.integers(1, 130))
    b = int(rng.integers(1, 5))
    hit = jnp.asarray(rng.random((b, r)) < rng.random())
    words = pack_hit_bitmap(hit)
    assert words.dtype == jnp.uint32
    assert words.shape == (b, hit_bitmap_words(r)) == (b, -(-r // 32))
    np.testing.assert_array_equal(np.asarray(unpack_hit_bitmap(words, r)),
                                  np.asarray(hit))


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_compact_expand_roundtrip_property(seed):
    """Property: expand(compact(hit, rows)) reproduces the rows of every
    KEPT slot bit-for-bit and zeros everywhere else, where kept is hit
    truncated to the first hit_cap hits per destination."""
    rng = np.random.default_rng(seed)
    b, r, d = (int(rng.integers(1, 5)), int(rng.integers(1, 80)),
               int(rng.integers(1, 6)))
    hit_cap = int(rng.integers(0, r + 20))
    hit = jnp.asarray(rng.random((b, r)) < rng.random())
    rows = jnp.asarray(rng.standard_normal((b, r, d)).astype(np.float32))
    rows = jnp.where(hit[..., None], rows, 0)
    kept, payload = compact_hit_rows(hit, rows, hit_cap)
    assert payload.shape == (b, min(hit_cap, r), d)
    # kept truncates each destination's hits at hit_cap, in slot order
    want_kept = np.asarray(hit) & (np.cumsum(np.asarray(hit), axis=-1)
                                   <= hit_cap)
    np.testing.assert_array_equal(np.asarray(kept), want_kept)
    out = expand_hit_rows(kept, payload)
    np.testing.assert_array_equal(
        np.asarray(out), np.where(want_kept[..., None], np.asarray(rows), 0))


def test_compact_zero_hit_batch_ships_empty_payload():
    """All-miss destination: the bitmap is all-zero words and the payload
    carries nothing but zeros — the compact response of a cold cache."""
    hit = jnp.zeros((3, 40), jnp.bool_)
    rows = jnp.ones((3, 40, 4))
    kept, payload = compact_hit_rows(hit, rows, 8)
    assert not np.asarray(kept).any()
    assert np.abs(np.asarray(payload)).max() == 0
    words = pack_hit_bitmap(kept)
    assert np.asarray(words).sum() == 0
    assert np.abs(np.asarray(expand_hit_rows(kept, payload))).max() == 0


def test_compact_all_hit_batch_payload_equals_rows():
    """All-hit destination at hit_cap == R: nothing demotes and the
    payload IS the dense response, in slot order."""
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((2, 24, 5)).astype(np.float32))
    hit = jnp.ones((2, 24), jnp.bool_)
    kept, payload = compact_hit_rows(hit, rows, 24)
    assert np.asarray(kept).all()
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(rows))
    np.testing.assert_array_equal(
        np.asarray(expand_hit_rows(kept, payload)), np.asarray(rows))


def test_compact_overflow_demotes_in_slot_order():
    """hit_cap overflow: exactly the FIRST hit_cap hits (slot order)
    survive; demoted slots read back as misses after the roundtrip —
    the requester owner-fetches them, never sees wrong rows."""
    hit = jnp.asarray([[True, False, True, True, True, False, True, True]])
    rows = jnp.arange(8, dtype=jnp.float32).reshape(1, 8, 1) + 1.0
    kept, payload = compact_hit_rows(hit, rows, 3)
    np.testing.assert_array_equal(
        np.asarray(kept),
        [[True, False, True, True, False, False, False, False]])
    np.testing.assert_array_equal(np.asarray(payload).ravel(), [1., 3., 4.])
    out = expand_hit_rows(kept, payload)
    np.testing.assert_array_equal(np.asarray(out).ravel(),
                                  [1., 0., 3., 4., 0., 0., 0., 0.])


def test_unpack_rejects_mismatched_word_count():
    with pytest.raises(ValueError):
        unpack_hit_bitmap(jnp.zeros((2, 3), jnp.uint32), 32)


def test_wire_config_validation():
    """CacheConfig and ModelConfig both reject unknown wire formats and
    negative hit caps at construction, and thread valid ones through."""
    from repro.core.config import ModelConfig

    with pytest.raises(ValueError):
        CacheConfig(64, wire="zstd").validated()
    with pytest.raises(ValueError):
        CacheConfig(64, hit_cap=-1).validated()
    cfg = CacheConfig(64, mode="tiered", l1_rows=8, wire="compact",
                      hit_cap=40).validated()
    # the wire travels with the L2 tier view (whose probe round it is)
    assert cfg.l2_config().wire == "compact"
    assert cfg.l2_config().hit_cap == 40
    with pytest.raises(ValueError):
        ModelConfig(name="x", family="gcn", cache_wire="zstd")
    with pytest.raises(ValueError):
        ModelConfig(name="x", family="gcn", cache_hit_cap=-2)
    m = ModelConfig(name="x", family="gcn", cache_rows=64,
                    cache_mode="sharded", cache_wire="dense", cache_hit_cap=7)
    cc = CacheConfig.from_model(m)
    assert cc.wire == "dense" and cc.hit_cap == 7


def test_zipf_wire_slot_reduction_meets_criterion():
    """Acceptance anchor: Zipf(1.1) stream, cache_rows=4096, >= 20
    iterations -> >= 30% fewer routed unique requests than cache-off."""
    from benchmarks.feature_cache import zipf_requests

    rng = np.random.default_rng(1)
    n, d, r, iters = 20_000, 4, 4_096, 20
    table = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    streams = [jnp.asarray(zipf_requests(rng, n, r)) for _ in range(iters)]
    base = 0
    for ids in streams:
        base += len(np.unique(np.asarray(ids)))
    cache = jax.tree.map(jnp.asarray, init_worker_caches(4096, d, 1))
    routed = 0
    for ids in streams:
        got, cache, (fs, _) = _run_fetch(table, ids, cache=cache, admit=2)
        routed += int(fs.n_unique[0])
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table)[np.asarray(ids)])
    assert routed < 0.7 * base, (routed, base)
