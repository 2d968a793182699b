"""Jit'd public wrappers for the Pallas kernels.

Each op dispatches kernel vs pure-jnp reference via ``use_kernel`` (models
pass their config's flag).  On non-TPU backends kernels run in
``interpret=True`` mode — the kernel body executes exactly, which is the
validation story on this CPU container; on TPU they compile natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .cache_gather import (cache_probe_compact_pallas,
                           cache_probe_gather_pallas,
                           cache_probe_tiered_pallas)
from .flash_attention import flash_attention_pallas
from .fanout_mean import fanout_mean_pallas
from .ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fanout_mean(x: jax.Array, mask: jax.Array, use_kernel: bool = False) -> jax.Array:
    """Masked mean over the fanout axis: x [M, K, D], mask [M, K] -> [M, D]
    (the GCN aggregation step on a padded fanout tree)."""
    if use_kernel:
        return fanout_mean_pallas(x, mask, interpret=_interpret())
    return ref.fanout_mean_ref(x, mask)


def cache_probe_gather(
    keys: jax.Array, rows: jax.Array, ids: jax.Array,
    assoc: int = 1, use_kernel: bool = False,
):
    """Fused hot-node cache probe+gather: (hit [R], rows [R, D])."""
    if use_kernel:
        return cache_probe_gather_pallas(keys, rows, ids, assoc=assoc,
                                         interpret=_interpret())
    return ref.cache_probe_gather_ref(keys, rows, ids, assoc=assoc)


def cache_probe_compact(
    keys: jax.Array, rows: jax.Array, ids: jax.Array,
    assoc: int = 1, hit_cap: int = 1, use_kernel: bool = False,
):
    """Fused probe + compact-wire encode of a [W, R] probe block:
    ``(words [W, ceil(R/32)] uint32, raw_words [W, ceil(R/32)] uint32,
    payload [W, min(hit_cap, R), D])`` — the post-demotion wire bitmap,
    the pre-demotion telemetry bitmap, and the compacted hit rows.

    The holder side of the compact shard-probe response
    (``generation._shard_probe`` with ``CacheConfig.wire == "compact"``);
    hits beyond ``hit_cap`` per destination are demoted to misses."""
    if use_kernel:
        return cache_probe_compact_pallas(keys, rows, ids, assoc=assoc,
                                          hit_cap=hit_cap,
                                          interpret=_interpret())
    return ref.cache_probe_compact_ref(keys, rows, ids, assoc=assoc,
                                       hit_cap=hit_cap)


def cache_probe_tiered(
    l1_keys: jax.Array, l1_rows: jax.Array,
    l2_keys: jax.Array, l2_rows: jax.Array, ids: jax.Array,
    l1_assoc: int = 1, l2_assoc: int = 1, use_kernel: bool = False,
):
    """Fused hierarchical L1/L2 probe: (src [R] 0=miss/1=L1/2=L2, rows)."""
    if use_kernel:
        return cache_probe_tiered_pallas(
            l1_keys, l1_rows, l2_keys, l2_rows, ids,
            l1_assoc=l1_assoc, l2_assoc=l2_assoc, interpret=_interpret())
    return ref.cache_probe_tiered_ref(l1_keys, l1_rows, l2_keys, l2_rows,
                                      ids, l1_assoc=l1_assoc,
                                      l2_assoc=l2_assoc)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True, use_kernel: bool = False,
    block_q: int = 128, block_k: int = 128,
) -> jax.Array:
    """Softmax attention with GQA head grouping: q [B, Hq, Lq, Dh],
    k/v [B, Hkv, Lk, Dh] -> [B, Hq, Lq, Dh] (online-softmax tiles when
    ``use_kernel``)."""
    if use_kernel:
        return flash_attention_pallas(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=_interpret(),
        )
    return ref.flash_attention_ref(q, k, v, causal=causal)


def ssd_scan(
    x: jax.Array, dt: jax.Array, a: jax.Array,
    b_mat: jax.Array, c_mat: jax.Array,
    use_kernel: bool = False, chunk: int = 128,
) -> jax.Array:
    """Mamba-2 SSD recurrence: x [B, L, H, P], dt [B, L, H], a [H],
    b/c [B, L, N] -> y [B, L, H, P] (chunked scan when ``use_kernel``)."""
    if use_kernel:
        return ssd_scan_pallas(x, dt, a, b_mat, c_mat, chunk=chunk,
                               interpret=_interpret())
    return ref.ssd_scan_ref(x, dt, a, b_mat, c_mat)
