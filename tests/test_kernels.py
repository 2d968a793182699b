"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.cache_gather import cache_probe_gather_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fanout_mean import fanout_mean_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.mark.parametrize("m,k,d", [(8, 4, 16), (37, 9, 130), (128, 20, 128), (5, 40, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fanout_mean(m, k, d, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k, d)).astype(dtype)
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.7, (m, k))
    got = fanout_mean_pallas(x, mask)
    want = ref.fanout_mean_ref(x, mask)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("c,d,r", [(64, 32, 17), (256, 128, 300), (1024, 96, 64)])
@pytest.mark.parametrize("assoc", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cache_probe_gather(c, d, r, assoc, dtype):
    """Fused VMEM probe+gather vs the jnp oracle across associativities:
    identical hit vector and bit-identical rows (the cache tier must never
    perturb features)."""
    from repro.core.feature_cache import hash_slots

    rng = np.random.default_rng(0)
    # residents installed at their TRUE hash sets spread over the ways (as
    # cache_insert would), plus ~half the slots left empty
    n_sets = c // assoc
    pool = rng.choice(50 * c, size=c, replace=False).astype(np.int32)
    sets = np.asarray(hash_slots(jnp.asarray(pool), n_sets))
    keys = np.full(c, -1, np.int32)
    way_fill = np.zeros(n_sets, np.int64)
    for pid, s in zip(pool, sets):
        if way_fill[s] < assoc:
            keys[s * assoc + way_fill[s]] = pid
            way_fill[s] += 1
    keys[rng.random(c) < 0.5] = -1
    keys = jnp.asarray(keys)
    rows = jax.random.normal(jax.random.PRNGKey(1), (c, d)).astype(dtype)
    # probe a mix of resident ids (hits) and random ids (mostly misses)
    ids = np.where(rng.random(r) < 0.5, rng.choice(pool, size=r),
                   rng.integers(0, 50 * c, r)).astype(np.int32)
    ids = jnp.asarray(ids)
    got_hit, got_rows = cache_probe_gather_pallas(keys, rows, ids, assoc=assoc)
    want_hit, want_rows = ref.cache_probe_gather_ref(keys, rows, ids,
                                                     assoc=assoc)
    np.testing.assert_array_equal(np.asarray(got_hit), np.asarray(want_hit))
    np.testing.assert_array_equal(
        np.asarray(got_rows, np.float32), np.asarray(want_rows, np.float32))
    assert np.asarray(want_hit).any() and not np.asarray(want_hit).all()


@pytest.mark.parametrize("assoc", [1, 2])
def test_cache_probe_gather_matches_state_probe(assoc):
    """The kernel and feature_cache.cache_probe(impl=...) agree — same hash,
    same rows — so either implementation can serve the fetch front end."""
    from repro.core.feature_cache import (CacheConfig, cache_probe,
                                          init_cache, cache_insert)

    cfg = CacheConfig(128, admit=1, assoc=assoc)
    cache = init_cache(128, 16)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, 400, 96, dtype=np.int32))
    rows = jax.random.normal(jax.random.PRNGKey(2), (96, 16))
    cache, _ = cache_insert(cache, ids, rows, jnp.ones(96, bool), cfg)
    probe = jnp.asarray(rng.integers(0, 400, 64, dtype=np.int32))
    hit_j, rows_j = cache_probe(cache, probe, cfg=cfg)
    hit_p, rows_p = cache_probe(cache, probe, cfg=cfg, impl="pallas")
    np.testing.assert_array_equal(np.asarray(hit_j), np.asarray(hit_p))
    np.testing.assert_array_equal(np.asarray(rows_j), np.asarray(rows_p))


def test_cache_probe_gather_degenerate_single_set():
    """c == assoc -> one set: the kernel takes the shift-guard branch
    (a literal 32-bit uint32 shift would be out of range)."""
    keys = jnp.asarray([11, 22, -1, 33], jnp.int32)
    rows = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    ids = jnp.asarray([22, 5, 33, 11, -7], jnp.int32)
    got_hit, got_rows = cache_probe_gather_pallas(keys, rows, ids, assoc=4)
    want_hit, want_rows = ref.cache_probe_gather_ref(keys, rows, ids, assoc=4)
    np.testing.assert_array_equal(np.asarray(got_hit), np.asarray(want_hit))
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    np.testing.assert_array_equal(np.asarray(want_hit),
                                  [True, False, True, True, False])


@pytest.mark.parametrize("c,d,w,r", [(64, 16, 4, 33), (256, 96, 2, 300),
                                     (1024, 32, 3, 64)])
@pytest.mark.parametrize("assoc", [1, 2, 4])
@pytest.mark.parametrize("hit_cap", [1, 16, 4096])
def test_cache_probe_compact(c, d, w, r, assoc, hit_cap):
    """Fused probe+compact vs the jnp oracle across associativities, probe
    shapes, and payload bounds (1 = heavy demotion, 4096 = clamped to R =
    never demotes): identical bitmap words and bit-identical payload."""
    from repro.kernels.cache_gather import cache_probe_compact_pallas
    from repro.core.feature_cache import hash_slots

    rng = np.random.default_rng(c + r + assoc)
    n_sets = c // assoc
    pool = rng.choice(10 * c, size=c, replace=False).astype(np.int32)
    sets = np.asarray(hash_slots(jnp.asarray(pool), n_sets))
    keys = np.full(c, -1, np.int32)
    way_fill = np.zeros(n_sets, np.int64)
    for pid, s in zip(pool, sets):
        if way_fill[s] < assoc:
            keys[s * assoc + way_fill[s]] = pid
            way_fill[s] += 1
    keys = jnp.asarray(keys)
    rows = jax.random.normal(jax.random.PRNGKey(1), (c, d))
    # resident ids (hits), random ids (mostly misses), and the -1 empty-
    # probe-slot sentinel, which must never alias an empty cache slot
    ids = np.where(rng.random((w, r)) < 0.5, rng.choice(pool, size=(w, r)),
                   rng.integers(0, 10 * c, (w, r))).astype(np.int32)
    ids[rng.random((w, r)) < 0.15] = -1
    ids = jnp.asarray(ids)
    got_w, got_raw, got_p = cache_probe_compact_pallas(
        keys, rows, ids, assoc=assoc, hit_cap=hit_cap)
    want_w, want_raw, want_p = ref.cache_probe_compact_ref(
        keys, rows, ids, assoc=assoc, hit_cap=hit_cap)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(got_raw), np.asarray(want_raw))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    assert got_w.shape == got_raw.shape == (w, -(-r // 32))
    assert got_p.shape == (w, min(hit_cap, r), d)


def test_cache_probe_compact_matches_dense_probe():
    """The compact encoding carries exactly the dense probe's hit rows
    (at a non-demoting hit_cap): unpacking the bitmap reproduces the
    dense hit vector and re-expanding the payload reproduces its rows —
    the wire format is pure transport, not a different probe."""
    from repro.core.feature_cache import (expand_hit_rows,
                                          unpack_hit_bitmap)
    from repro.kernels.cache_gather import cache_probe_compact_pallas

    rng = np.random.default_rng(9)
    c, d, r = 128, 12, 96
    keys = np.full(c, -1, np.int32)
    occ = rng.random(c) < 0.5
    keys[occ] = rng.integers(0, 4 * c, occ.sum())
    keys = jnp.asarray(keys)
    rows = jax.random.normal(jax.random.PRNGKey(4), (c, d))
    ids = jnp.asarray(rng.integers(0, 4 * c, (3, r)).astype(np.int32))
    words, raw_words, payload = cache_probe_compact_pallas(keys, rows, ids,
                                                           hit_cap=r)
    want_hit, want_rows = jax.vmap(
        lambda i: ref.cache_probe_gather_ref(keys, rows, i))(ids)
    np.testing.assert_array_equal(
        np.asarray(unpack_hit_bitmap(words, r)), np.asarray(want_hit))
    # at a non-demoting hit_cap the raw and wire bitmaps coincide
    np.testing.assert_array_equal(np.asarray(raw_words), np.asarray(words))
    np.testing.assert_array_equal(
        np.asarray(expand_hit_rows(unpack_hit_bitmap(words, r), payload)),
        np.asarray(want_rows))


def test_cache_probe_compact_degenerate_single_set():
    """c == assoc -> one set: the compact kernel takes the shift-guard
    branch (a literal 32-bit uint32 shift would be out of range)."""
    from repro.kernels.cache_gather import cache_probe_compact_pallas

    keys = jnp.asarray([11, 22, -1, 33], jnp.int32)
    rows = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
    ids = jnp.asarray([[22, 5, 33, 11, -7]], jnp.int32)
    got_w, got_raw, got_p = cache_probe_compact_pallas(keys, rows, ids,
                                                       assoc=4, hit_cap=2)
    want_w, want_raw, want_p = ref.cache_probe_compact_ref(
        keys, rows, ids, assoc=4, hit_cap=2)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(got_raw), np.asarray(want_raw))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    # hits at slots 0 and 2 survive the 2-row bound; the slot-3 hit
    # demotes (cleared on the wire, still set in the raw telemetry)
    assert np.asarray(got_w).ravel().tolist() == [0b101]
    assert np.asarray(got_raw).ravel().tolist() == [0b1101]


@pytest.mark.parametrize("b,hq,hkv,lq,lk,dh", [
    (1, 2, 2, 128, 128, 32),     # MHA square
    (2, 4, 2, 128, 256, 64),     # GQA, decode-style longer k
    (1, 8, 1, 256, 256, 64),     # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, hq, hkv, lq, lk, dh, causal):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, lq, dh))
    k = jax.random.normal(ks[1], (b, hkv, lk, dh))
    v = jax.random.normal(ks[2], (b, hkv, lk, dh))
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 3, 16, 8, 16),
    (1, 128, 1, 32, 16, 128),    # single chunk == full quadratic path
])
def test_ssd_scan(b, l, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, l, n))
    cm = jax.random.normal(ks[4], (b, l, n))
    got = ssd_scan_pallas(x, dt, a, bm, cm, chunk=chunk)
    want = ref.ssd_scan_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_ssd_chunk_invariance():
    """Output must not depend on the chunk size (the SSD identity)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = jax.random.normal(ks[0], (1, 64, 2, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 2)))
    a = -jnp.exp(jax.random.normal(ks[2], (2,)))
    bm = jax.random.normal(ks[3], (1, 64, 4))
    cm = jax.random.normal(ks[4], (1, 64, 4))
    outs = [np.asarray(ssd_scan_pallas(x, dt, a, bm, cm, chunk=c))
            for c in (8, 16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("c1,c2,r", [(16, 64, 33), (64, 256, 300),
                                     (32, 1024, 96)])
@pytest.mark.parametrize("l1_assoc,l2_assoc", [(1, 1), (2, 4), (2, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cache_probe_tiered(c1, c2, r, l1_assoc, l2_assoc, dtype):
    """Fused two-tier probe vs the jnp oracle: identical source vector
    (0 = miss, 1 = L1, 2 = L2 — the L1 wins a double residency) and
    bit-identical rows across tier sizes, associativities, and dtypes."""
    from repro.kernels.cache_gather import cache_probe_tiered_pallas

    d = 24
    rng = np.random.default_rng(c1 + c2 + r)
    ids = jnp.asarray(rng.integers(0, 4 * c2, r).astype(np.int32))

    def fill(c, frac):
        keys = np.full(c, -1, np.int32)
        occ = rng.random(c) < frac
        keys[occ] = rng.integers(0, 4 * c2, occ.sum())
        rows = rng.standard_normal((c, d)).astype(np.float32)
        return jnp.asarray(keys), jnp.asarray(rows, dtype)

    l1k, l1r = fill(c1, 0.6)
    l2k, l2r = fill(c2, 0.5)
    got_src, got_rows = cache_probe_tiered_pallas(
        l1k, l1r, l2k, l2r, ids, l1_assoc=l1_assoc, l2_assoc=l2_assoc)
    want_src, want_rows = ref.cache_probe_tiered_ref(
        l1k, l1r, l2k, l2r, ids, l1_assoc=l1_assoc, l2_assoc=l2_assoc)
    np.testing.assert_array_equal(np.asarray(got_src), np.asarray(want_src))
    np.testing.assert_array_equal(np.asarray(got_rows, np.float32),
                                  np.asarray(want_rows, np.float32))


def test_cache_probe_tiered_degenerate_single_set_l1():
    """A 1-row (single-set) L1 in front of a normal L2 exercises the
    32-bit-shift guard on the L1 side of the fused kernel."""
    from repro.kernels.cache_gather import cache_probe_tiered_pallas

    l1k = jnp.asarray([42], jnp.int32)
    l1r = jnp.asarray([[7.0, 8.0]])
    l2k = jnp.asarray([42, 9, -1, -1], jnp.int32)
    l2r = jnp.asarray(np.arange(8, dtype=np.float32).reshape(4, 2))
    ids = jnp.asarray([42, 9, 3], jnp.int32)
    got_src, got_rows = cache_probe_tiered_pallas(l1k, l1r, l2k, l2r, ids)
    want_src, want_rows = ref.cache_probe_tiered_ref(l1k, l1r, l2k, l2r, ids)
    np.testing.assert_array_equal(np.asarray(got_src), np.asarray(want_src))
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))
    assert int(got_src[0]) == 1          # resident in both tiers -> L1 wins


def test_cache_probe_tiered_matches_state_probe():
    """ops.cache_probe_tiered (kernel) and feature_cache.tiered_probe
    (production jnp path) agree on a populated TieredCache state."""
    from repro.core.feature_cache import (CacheConfig, TieredCache,
                                          cache_insert, init_cache,
                                          tiered_probe)

    cfg = CacheConfig(128, admit=1, assoc=4, mode="tiered", l1_rows=16,
                      l1_promote=1).validated()
    rng = np.random.default_rng(11)
    l1, l2 = init_cache(16, 8), init_cache(128, 8)
    ids1 = jnp.asarray(rng.integers(0, 500, 12).astype(np.int32))
    ids2 = jnp.asarray(rng.integers(0, 500, 96).astype(np.int32))
    l1, _ = cache_insert(l1, ids1, jax.random.normal(jax.random.PRNGKey(0), (12, 8)),
                         jnp.ones(12, bool), cfg.l1_config())
    l2, _ = cache_insert(l2, ids2, jax.random.normal(jax.random.PRNGKey(1), (96, 8)),
                         jnp.ones(96, bool), cfg.l2_config())
    state = TieredCache(l1=l1, l2=l2)
    probe = jnp.asarray(rng.integers(0, 500, 64).astype(np.int32))
    j1, j2, jr = tiered_probe(state, probe, cfg=cfg, impl="jnp")
    p1, p2, pr = tiered_probe(state, probe, cfg=cfg, impl="pallas")
    np.testing.assert_array_equal(np.asarray(j1), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(j2), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(jr), np.asarray(pr))
    assert bool(np.asarray(j1).any()) and bool(np.asarray(j2).any())
