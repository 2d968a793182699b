"""Fused Pallas probe+gather kernels for the hot-node feature cache.

They serve *cache hits* from the device-resident cache
(core/feature_cache.py) in one kernel each, with no vector gather, which
the TPU compiler does not lower.  For a block of probes laid along the
lanes (``ids [1, n]``) against the cache keys laid along the sublanes
(``keys [C, 1]``):

  set    = top-bits multiplicative hash of each id          (VPU)
  match  = keys[c] == id  and  c // assoc == set            ([C, n] compare)
  first  = lowest matching slot per probe (the first way)   (sublane min)
  rows   = one-hot[C, n]^T @ rows[C, D]                     (MXU)

The one-hot product moves rows bit-exactly: the wrapper splits f32 rows
into three bfloat16 pieces whose f32 sum is the row (``_exact_pieces``),
each piece is selected by a 0/1 matrix with at most one 1 per output row,
and the pieces are summed back in f32.  Rows must be finite.

``cache_probe_gather_pallas`` is the one-tier probe, ``cache_probe_
tiered_pallas`` probes the small replicated L1 and this worker's L2 block
in the same VMEM residency (L1 wins a double hit), and
``cache_probe_compact_pallas`` is the holder side of the compact
shard-probe wire: it emits packed hit bitmaps and the rank-compacted hit
rows directly, tiled over the probe axis so that VMEM holds one chunk of
probes and one payload window at a time, whatever the probe capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# keep the hash bit-compatible with the jnp probe (core/feature_cache.py)
from ..core.feature_cache import (_HASH_K, VALID_ASSOC, WIRE_WORD_BITS,
                                  hit_bitmap_words)

_LANES = 128
#: probe rows per grid step of the compact kernel (each row is one lane
#: vector of 128 probes)
_COMPACT_ROWS = 8
#: VMEM budget: a [C, 128] compare per probe block plus the cache block
_VMEM_LIMIT = 96 * 1024 * 1024


def _shift_for(n_sets: int) -> int:
    """Hash shift for a power-of-two set count; 32 signals the degenerate
    single-set cache (a literal 32-bit shift would be out of range for
    uint32 — ``_sets_of`` short-circuits to set 0 instead, mirroring
    feature_cache.hash_slots).  Shared by all probe kernels so their
    hashes cannot silently diverge."""
    return 32 if n_sets == 1 else 32 - (int(n_sets).bit_length() - 1)


def _sets_of(ids, shift: int):
    """Set index of each id inside a kernel body (static ``shift``)."""
    if shift >= 32:
        return jnp.zeros(ids.shape, jnp.int32)
    h = ids.astype(jnp.uint32) * jnp.uint32(_HASH_K)
    return jax.lax.shift_right_logical(h, jnp.uint32(shift)).astype(jnp.int32)


def _check_cache(c: int, assoc: int, name: str = "cache") -> None:
    if c & (c - 1):
        raise ValueError(f"{name} size must be a power of two, got {c}")
    if assoc not in VALID_ASSOC or assoc > c:
        raise ValueError(f"{name} assoc must be one of {VALID_ASSOC} and "
                         f"<= {c}, got {assoc}")


def _top_bf16(x: jax.Array) -> jax.Array:
    """``x`` (f32) truncated to its bfloat16-representable top 16 bits.

    A mask, not a round trip through bfloat16: XLA may drop an
    f32 -> bf16 -> f32 convert pair as excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _exact_pieces(rows: jax.Array) -> jax.Array:
    """[P, C, D] bfloat16 pieces whose f32 sum, taken in order, is exactly
    ``rows``: one piece for bfloat16 rows, three for (finite) f32 rows.

    Each f32 piece holds at most 8 significant bits, so its conversion to
    bfloat16 is exact."""
    if rows.dtype == jnp.bfloat16:
        return rows[None]
    if rows.dtype != jnp.float32:
        raise ValueError(f"cache rows must be float32 or bfloat16, got "
                         f"{rows.dtype}")
    hi = _top_bf16(rows)
    rest = rows - hi
    mid = _top_bf16(rest)
    return jnp.stack([hi, mid, rest - mid]).astype(jnp.bfloat16)


def _first_match(keys, ids, shift: int, assoc: int):
    """``(onehot [C, n] bool, hit [1, n] bool)`` of probes ``ids [1, n]``
    against ``keys [C, 1]``: column ``s`` of ``onehot`` marks the first
    way of the probe's set whose key equals it."""
    c = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)         # [C, 1]
    set_of_slot = jax.lax.shift_right_logical(
        c, jnp.int32(assoc.bit_length() - 1))
    match = jnp.logical_and(keys == ids,
                            set_of_slot == _sets_of(ids, shift))   # [C, n]
    first = jnp.min(jnp.where(match, c, keys.shape[0]), axis=0,
                    keepdims=True)                                  # [1, n]
    return c == first, first < keys.shape[0]


def _select_rows(onehot, pieces_ref):
    """``onehot [C, n]^T @ rows`` in f32 from the exact bf16 pieces."""
    sel = onehot.astype(jnp.bfloat16)
    out = None
    for k in range(pieces_ref.shape[0]):
        part = jax.lax.dot_general(
            sel, pieces_ref[k], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _pad_lanes(ids: jax.Array, multiple: int) -> jax.Array:
    """Pad the last axis of ``ids`` with the -1 sentinel to ``multiple``."""
    pad = -ids.shape[-1] % multiple
    if not pad:
        return ids
    return jnp.concatenate(
        [ids, jnp.full(ids.shape[:-1] + (pad,), -1, ids.dtype)], axis=-1)


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _probe_gather_kernel(keys_ref, pieces_ref, ids_ref, hit_ref, out_ref,
                         *, shift: int, assoc: int):
    onehot, hit = _first_match(keys_ref[...], ids_ref[...], shift, assoc)
    hit_ref[...] = hit.astype(jnp.int32)
    out_ref[...] = _select_rows(onehot, pieces_ref).astype(out_ref.dtype)


def cache_probe_gather_pallas(
    keys: jax.Array,     # [C] int32 resident id per slot (-1 = empty)
    rows: jax.Array,     # [C, D] resident feature rows
    ids: jax.Array,      # [R] int32 probe ids
    *,
    assoc: int = 1,
    block_r: int = _LANES,
    block_d: int = 128,
    interpret: bool = True,
):
    """Probe ``ids`` against an ``assoc``-way cache: ``(hit [R], out [R, D])``.

    ``out`` rows are the cached copies where hit, zeros where missed —
    bit-identical to ``feature_cache.cache_probe`` (the jnp oracle is
    ``ref.cache_probe_gather_ref``).  Grid: (R blocks of ``block_r``
    probes, a multiple of 128; D blocks).
    """
    c = keys.shape[0]
    _check_cache(c, assoc)
    r, d = ids.shape[0], rows.shape[1]
    br, bd = block_r, min(block_d, d)
    ids_p = _pad_lanes(ids.reshape(1, r), br)
    rp = ids_p.shape[1]
    pieces = _exact_pieces(rows)
    hit, out = pl.pallas_call(
        functools.partial(_probe_gather_kernel, shift=_shift_for(c // assoc),
                          assoc=assoc),
        grid=(pl.cdiv(rp, br), pl.cdiv(d, bd)),
        in_specs=[
            pl.BlockSpec((c, 1), lambda i, j: (0, 0)),            # all keys
            pl.BlockSpec((pieces.shape[0], c, bd), lambda i, j: (0, 0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, br), lambda i, j: (0, i)),
            pl.BlockSpec((br, bd), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rp), jnp.int32),
            jax.ShapeDtypeStruct((rp, d), rows.dtype),
        ],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(keys.reshape(c, 1), pieces, ids_p)
    return hit[0, :r].astype(jnp.bool_), out[:r]


def _pack_words(v):
    """Pack a [1, 128] bool lane vector into its [1, 4] int32 bitmap words
    (bit ``s % 32`` of word ``s // 32``).  Each word is assembled from two
    16-bit halves, so every MXU partial sum is exact in f32."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 4), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 4), 1)
    bit = jnp.bitwise_and(lane, WIRE_WORD_BITS - 1)
    own = jax.lax.shift_right_logical(lane, jnp.int32(5)) == word
    x = v.astype(jnp.bfloat16)

    def half(lo: int):
        w = jnp.where(jnp.logical_and(own, (bit >= lo) & (bit < lo + 16)),
                      jax.lax.shift_left(jnp.int32(1), bit - lo), 0)
        return jnp.dot(x, w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    return jnp.bitwise_or(half(0), jax.lax.shift_left(half(16),
                                                      jnp.int32(16)))


def _probe_compact_kernel(keys_ref, pieces_ref, ids_ref, words_ref, raw_ref,
                          pay_hbm, win_ref, state_ref, sem, *, shift: int,
                          assoc: int, hit_cap: int, n_tiles: int):
    # state_ref: [0] hits counted so far for this destination, [1] the
    # payload tile held in the first half of the window
    dest, col = pl.program_id(0), pl.program_id(1)
    bd = win_ref.shape[1]

    def put(src, tile):
        # copy one 128-row window half to payload tile ``tile`` in HBM
        cp = pltpu.make_async_copy(
            src, pay_hbm.at[dest, pl.ds(tile * _LANES, _LANES),
                            pl.ds(col * bd, bd)], sem)
        cp.start()
        cp.wait()

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[0] = 0
        state_ref[1] = 0
        win_ref[...] = jnp.zeros(win_ref.shape, win_ref.dtype)

    keys = keys_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    upper = (row <= col_i).astype(jnp.bfloat16)        # inclusive prefix
    q = jax.lax.broadcasted_iota(jnp.int32, (_LANES, 1), 0)
    def probe_row(i, carry):                           # 128 probes per row
        ids = ids_ref[0, pl.ds(i, 1), :]               # [1, 128]
        onehot, hit = _first_match(keys, ids, shift, assoc)
        # empty probe slots carry -1, which must not alias empty cache
        # slots (their resident key is also -1)
        hit = jnp.logical_and(hit, ids >= 0)
        base = state_ref[0]
        # rank of each hit among this destination's hits, 1-based; the
        # first hit_cap hits in slot order are kept, later ones demoted
        rank = base + jnp.dot(hit.astype(jnp.bfloat16), upper,
                              preferred_element_type=jnp.float32
                              ).astype(jnp.int32)
        kept = jnp.logical_and(hit, rank <= hit_cap)
        # ``kept`` is the wire bitmap; ``hit`` (pre-demotion) stays on the
        # holder as the demotion/hit-peak telemetry
        words_ref[0, pl.ds(i, 1), :] = _pack_words(kept)
        raw_ref[0, pl.ds(i, 1), :] = _pack_words(hit)
        state_ref[0] = base + jnp.sum(hit.astype(jnp.int32))

        @pl.when(base < hit_cap)
        def _():
            # the kept ranks of this row lie in [base, base + 128), inside
            # the window's two tiles once it has moved to base's tile;
            # base grows by at most 128 per row, so it moves at most once
            tile = base // _LANES

            @pl.when(tile > state_ref[1])
            def _():
                put(win_ref.at[pl.ds(0, _LANES)], state_ref[1])
                win_ref[pl.ds(0, _LANES), :] = win_ref[pl.ds(_LANES, _LANES), :]
                win_ref[pl.ds(_LANES, _LANES), :] = jnp.zeros(
                    (_LANES, bd), win_ref.dtype)
                state_ref[1] = tile

            sel = jnp.logical_and(onehot, kept).astype(jnp.bfloat16)
            for k in range(2):
                place = jnp.logical_and(q + (tile + k) * _LANES == rank - 1,
                                        kept)
                # slot of each payload row: [128 rows, C]
                slot = jax.lax.dot_general(
                    place.astype(jnp.bfloat16), sel,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32
                ).astype(jnp.bfloat16)
                part = None
                for p in range(pieces_ref.shape[0]):
                    y = jnp.dot(slot, pieces_ref[p],
                                preferred_element_type=jnp.float32)
                    part = y if part is None else part + y
                win_ref[pl.ds(k * _LANES, _LANES), :] += part
        return carry

    jax.lax.fori_loop(0, ids_ref.shape[1], probe_row, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        # the window's two tiles, then zeros for every tile past them
        cur = state_ref[1]
        put(win_ref.at[pl.ds(0, _LANES)], cur)
        put(win_ref.at[pl.ds(_LANES, _LANES)], cur + 1)
        win_ref[pl.ds(0, _LANES), :] = jnp.zeros((_LANES, bd), win_ref.dtype)

        def zero_tile(t, carry):
            put(win_ref.at[pl.ds(0, _LANES)], t)
            return carry

        jax.lax.fori_loop(cur + 2, n_tiles, zero_tile, 0)


def cache_probe_compact_pallas(
    keys: jax.Array,     # [C] int32 resident id per slot (-1 = empty)
    rows: jax.Array,     # [C, D] resident feature rows
    ids: jax.Array,      # [W, R] int32 probe ids, one row per destination
                         # (-1 = empty probe slot)
    *,
    assoc: int = 1,
    hit_cap: int = 1,
    block_d: int = 128,
    interpret: bool = True,
):
    """Fused probe + compact-wire encode for the shard-probe response.

    Probes every destination's [R] probe block against the ``assoc``-way
    cache and emits the compact wire format directly — ``(words
    [W, ceil(R/32)] uint32, raw_words [W, ceil(R/32)] uint32, payload
    [W, min(hit_cap, R), D])`` — without ever materializing the dense
    [W, R, D] response block (the point: the dense block is exactly what
    the compact wire exists to not ship).  ``words`` is the
    post-demotion bitmap that rides the wire; ``raw_words`` packs the
    PRE-demotion hits and stays on the holder (the
    ``n_probe_demoted``/``probe_hit_peak`` telemetry — emitting it from
    the same probe avoids a second keys pass).  Bit-identical to
    ``ref.cache_probe_compact_ref``; hits beyond ``hit_cap`` per
    destination are demoted (bit cleared, row dropped), matching the
    holder side of ``generation._shard_probe``.

    Grid: (W destinations, D blocks, R chunks of 1024 probes).  The chunk
    axis runs in order and carries the destination's running hit count,
    so each row of 128 probes puts its kept hits into a two-tile (256-row)
    VMEM window of the payload; tiles the window has passed are copied to
    the payload in HBM.  VMEM holds the cache block, one [C, 128] compare
    and the window, whatever R and ``hit_cap`` are.
    """
    c = keys.shape[0]
    _check_cache(c, assoc)
    if ids.ndim != 2:
        raise ValueError(f"ids must be [W, R] (one row per destination), "
                         f"got shape {tuple(ids.shape)}")
    w, r = ids.shape
    if r < 1 or w < 1:
        raise ValueError(f"need at least one destination and one probe "
                         f"slot, got ids shape {tuple(ids.shape)}")
    hit_cap = min(hit_cap, r)
    if hit_cap < 1:
        raise ValueError("hit_cap must be >= 1 (a zero-row payload cannot "
                         "ship hits; use the dense wire to disable)")
    n_words = hit_bitmap_words(r)
    chunk = _COMPACT_ROWS * _LANES
    ids_p = _pad_lanes(ids, chunk).reshape(w, -1, _LANES)
    n_rows = ids_p.shape[1]
    d = rows.shape[1]
    # payload columns go out by DMA in whole blocks
    bd = block_d if d % block_d == 0 else d
    # the payload is written in whole 128-row tiles; the window's second
    # tile may run one tile past hit_cap
    n_tiles = -(-hit_cap // _LANES) + 1
    pieces = _exact_pieces(rows)
    words, raw, pay = pl.pallas_call(
        functools.partial(_probe_compact_kernel, shift=_shift_for(c // assoc),
                          assoc=assoc, hit_cap=hit_cap, n_tiles=n_tiles),
        grid=(w, pl.cdiv(d, bd), pl.cdiv(n_rows, _COMPACT_ROWS)),
        in_specs=[
            pl.BlockSpec((c, 1), lambda i, j, t: (0, 0)),         # all keys
            pl.BlockSpec((pieces.shape[0], c, bd),
                         lambda i, j, t: (0, 0, j)),
            pl.BlockSpec((1, _COMPACT_ROWS, _LANES),
                         lambda i, j, t: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, _COMPACT_ROWS, 4), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, _COMPACT_ROWS, 4), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),                    # HBM
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w, n_rows, 4), jnp.int32),
            jax.ShapeDtypeStruct((w, n_rows, 4), jnp.int32),
            jax.ShapeDtypeStruct((w, n_tiles * _LANES, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2 * _LANES, bd), jnp.float32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(keys.reshape(c, 1), pieces, ids_p)

    def words_of(x):
        x = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return x.reshape(w, -1)[:, :n_words]

    return (words_of(words), words_of(raw),
            pay[:, :hit_cap].astype(rows.dtype))


def _probe_tiered_kernel(l1k_ref, l1p_ref, l2k_ref, l2p_ref, ids_ref,
                         src_ref, out_ref, *, shift1: int, shift2: int,
                         l1_assoc: int, l2_assoc: int):
    ids = ids_ref[...]
    oh1, hit1 = _first_match(l1k_ref[...], ids, shift1, l1_assoc)
    oh2, hit2 = _first_match(l2k_ref[...], ids, shift2, l2_assoc)
    # L1 takes priority on a double hit
    oh2 = jnp.logical_and(oh2, ~hit1)
    src_ref[...] = jnp.where(hit1, 1, jnp.where(hit2, 2, 0)).astype(jnp.int32)
    out = _select_rows(oh1, l1p_ref) + _select_rows(oh2, l2p_ref)
    out_ref[...] = out.astype(out_ref.dtype)


def cache_probe_tiered_pallas(
    l1_keys: jax.Array,  # [C1] int32 L1 resident id per slot (-1 = empty)
    l1_rows: jax.Array,  # [C1, D] L1 resident feature rows
    l2_keys: jax.Array,  # [C2] int32 L2 resident id per slot
    l2_rows: jax.Array,  # [C2, D] L2 resident feature rows
    ids: jax.Array,      # [R] int32 probe ids
    *,
    l1_assoc: int = 1,
    l2_assoc: int = 1,
    block_r: int = _LANES,
    block_d: int = 128,
    interpret: bool = True,
):
    """Fused two-tier probe: ``(src [R] int32, out [R, D])``.

    ``src`` is 0 where both tiers miss, 1 where the L1 serves the id, 2
    where (only) the L2 does; ``out`` carries the serving tier's row copy,
    zeros on a miss.  Bit-identical to ``ref.cache_probe_tiered_ref`` and
    to ``feature_cache.tiered_probe``'s jnp path.
    """
    c1, c2 = l1_keys.shape[0], l2_keys.shape[0]
    _check_cache(c1, l1_assoc, "l1")
    _check_cache(c2, l2_assoc, "l2")
    if l1_rows.shape[1] != l2_rows.shape[1]:
        raise ValueError(f"tier row widths differ: {l1_rows.shape[1]} vs "
                         f"{l2_rows.shape[1]}")
    r, d = ids.shape[0], l2_rows.shape[1]
    br, bd = block_r, min(block_d, d)
    ids_p = _pad_lanes(ids.reshape(1, r), br)
    rp = ids_p.shape[1]
    p1 = _exact_pieces(l1_rows.astype(l2_rows.dtype))
    p2 = _exact_pieces(l2_rows)
    src, out = pl.pallas_call(
        functools.partial(_probe_tiered_kernel,
                          shift1=_shift_for(c1 // l1_assoc),
                          shift2=_shift_for(c2 // l2_assoc),
                          l1_assoc=l1_assoc, l2_assoc=l2_assoc),
        grid=(pl.cdiv(rp, br), pl.cdiv(d, bd)),
        in_specs=[
            pl.BlockSpec((c1, 1), lambda i, j: (0, 0)),           # L1 keys
            pl.BlockSpec((p1.shape[0], c1, bd), lambda i, j: (0, 0, j)),
            pl.BlockSpec((c2, 1), lambda i, j: (0, 0)),           # L2 keys
            pl.BlockSpec((p2.shape[0], c2, bd), lambda i, j: (0, 0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, br), lambda i, j: (0, i)),
            pl.BlockSpec((br, bd), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rp), jnp.int32),
            jax.ShapeDtypeStruct((rp, d), l2_rows.dtype),
        ],
        compiler_params=_params("parallel", "parallel"),
        interpret=interpret,
    )(l1_keys.reshape(c1, 1), p1, l2_keys.reshape(c2, 1), p2, ids_p)
    return src[0, :r], out[:r]
