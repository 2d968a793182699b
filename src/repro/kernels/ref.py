"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Each function here is the semantic ground truth; kernel tests sweep shapes
and dtypes and ``assert_allclose`` the Pallas output (interpret=True on this
CPU container; TPU is the compile target) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fanout_mean_ref(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Masked mean over the fanout axis: x [M, K, D], mask [M, K] -> [M, D].

    The GCN aggregation step on a padded fanout tree (paper §3 model)."""
    m = mask.astype(x.dtype)
    num = jnp.einsum("mkd,mk->md", x, m)
    den = jnp.maximum(m.sum(axis=1, keepdims=True), 1.0)
    return num / den


def cache_probe_gather_ref(
    keys: jax.Array, rows: jax.Array, ids: jax.Array, assoc: int = 1
) -> tuple:
    """Set-associative cache probe: keys [C], rows [C, D], ids [R] ->
    (hit [R] bool, out [R, D]); out is the cached row where hit, zeros
    where missed.  Set ``s = hash(id) mod (C/assoc)`` owns the ``assoc``
    consecutive slots ``s*assoc + j``; ``assoc=1`` is the direct-mapped
    special case.  Semantic ground truth for the fused probe+gather kernel
    (and the shape the jnp probe in core/feature_cache.py takes)."""
    from ..core.feature_cache import hash_slots
    sets = hash_slots(ids, keys.shape[0] // assoc)
    slots = sets[:, None] * assoc + jnp.arange(assoc)[None, :]   # [R, A]
    match = keys[slots] == ids[:, None]
    hit = match.any(axis=-1)
    way = jnp.argmax(match, axis=-1)
    out = jnp.where(hit[:, None], rows[sets * assoc + way], 0)
    return hit, out


def cache_probe_compact_ref(
    keys: jax.Array, rows: jax.Array, ids: jax.Array,
    assoc: int = 1, hit_cap: int = 1,
) -> tuple:
    """Fused probe + compact-wire encode: keys [C], rows [C, D],
    ids [W, R] -> ``(words [W, ceil(R/32)] uint32, raw_words
    [W, ceil(R/32)] uint32, payload [W, hc, D])`` with
    ``hc = min(hit_cap, R)``.

    Per destination row ``w``: probe the ``assoc``-way cache exactly as
    ``cache_probe_gather_ref`` does (ids ``< 0`` never hit — they are the
    empty-probe-slot sentinel and must not alias empty cache slots, whose
    resident key is also -1), KEEP the first ``hit_cap`` hits in slot
    order (later hits are demoted to misses — their bits are cleared and
    their rows never enter the payload), pack the kept vector into uint32
    bitmap words (bit ``s % 32`` of word ``s // 32``), and gather the
    kept rows into the ``p``-th payload slot by hit rank, zeros beyond
    the kept count.  ``raw_words`` packs the PRE-demotion hit vector —
    the holder-side demotion/hit-peak telemetry.  Semantic ground truth
    for the fused probe+compact kernel (``cache_probe_compact_pallas``)
    and for the holder side of the compact shard-probe wire
    (``generation._shard_probe``)."""
    from ..core.feature_cache import compact_hit_rows, pack_hit_bitmap
    hit, out = jax.vmap(
        lambda i: cache_probe_gather_ref(keys, rows, i, assoc=assoc))(ids)
    hit = jnp.logical_and(hit, ids >= 0)
    out = jnp.where(hit[..., None], out, 0)
    kept, payload = compact_hit_rows(hit, out, hit_cap)
    return pack_hit_bitmap(kept), pack_hit_bitmap(hit), payload


def cache_probe_tiered_ref(
    l1_keys: jax.Array, l1_rows: jax.Array,
    l2_keys: jax.Array, l2_rows: jax.Array,
    ids: jax.Array, l1_assoc: int = 1, l2_assoc: int = 1,
) -> tuple:
    """Hierarchical two-tier cache probe: ``(src [R] int32, out [R, D])``.

    Probes the small replicated L1 and the local L2 block in one pass —
    the L1 takes priority on a double hit.  ``src`` reports the serving
    tier (0 = miss, 1 = L1, 2 = L2); ``out`` is the serving tier's row
    copy, zeros where both tiers miss.  Semantic ground truth for the
    fused tiered probe kernel (``cache_probe_tiered_pallas``) and the
    shape ``feature_cache.tiered_probe``'s jnp path takes."""
    l1_hit, l1_out = cache_probe_gather_ref(l1_keys, l1_rows, ids,
                                            assoc=l1_assoc)
    l2_hit, l2_out = cache_probe_gather_ref(l2_keys, l2_rows, ids,
                                            assoc=l2_assoc)
    src = jnp.where(l1_hit, 1, jnp.where(l2_hit, 2, 0)).astype(jnp.int32)
    out = jnp.where(l1_hit[:, None], l1_out,
                    jnp.where(l2_hit[:, None], l2_out, 0))
    return src, out


def flash_attention_ref(
    q: jax.Array,      # [B, Hq, Lq, Dh]
    k: jax.Array,      # [B, Hkv, Lk, Dh]
    v: jax.Array,      # [B, Hkv, Lk, Dh]
    causal: bool = True,
) -> jax.Array:
    """Exact softmax attention with GQA head grouping."""
    b, hq, lq, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, lq, dh)
    scale = 1.0 / jnp.sqrt(dh).astype(q.dtype)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * scale
    if causal:
        lk = k.shape[2]
        qi = jnp.arange(lq)[:, None] + (lk - lq)   # align last q with last k
        ki = jnp.arange(lk)[None, :]
        logits = jnp.where(qi >= ki, logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v)
    return out.reshape(b, hq, lq, dh)


def ssd_scan_ref(
    x: jax.Array,      # [B, L, H, P]
    dt: jax.Array,     # [B, L, H]        (post-softplus, > 0)
    a: jax.Array,      # [H]              (negative: decay log-rate)
    b_mat: jax.Array,  # [B, L, N]        (single group, broadcast over heads)
    c_mat: jax.Array,  # [B, L, N]
) -> jax.Array:
    """Mamba-2 SSD recurrence, exact sequential oracle:

        h_t = exp(a * dt_t) * h_{t-1} + dt_t * (b_t  outer  x_t)
        y_t = h_t @ c_t
    """
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]

    def step(h_state, inp):
        xt, dtt, bt, ct = inp                      # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(a[None, :] * dtt)          # [B, H]
        upd = dtt[..., None, None] * (
            xt[..., :, None] * bt[:, None, None, :]
        )                                           # [B, H, P, N]
        h_state = h_state * decay[..., None, None] + upd
        yt = jnp.einsum("bhpn,bn->bhp", h_state, ct)
        return h_state, yt

    h0 = jnp.zeros((bsz, h, p, n), x.dtype)
    xs = (
        jnp.moveaxis(x, 1, 0),
        jnp.moveaxis(dt, 1, 0),
        jnp.moveaxis(b_mat, 1, 0),
        jnp.moveaxis(c_mat, 1, 0),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1)                  # [B, L, H, P]
