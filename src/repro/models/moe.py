"""Mixture-of-Experts layer (sort-based capacity dispatch) and the
qwen3-moe-30b-a3b model (48L all-MoE, 128 experts top-8, GQA attention).

Dispatch is the production-standard capacity-factor scheme (GShard/Switch
lineage): token->expert assignments are sorted by expert, each token takes
its rank within its expert's queue, ranks beyond capacity are dropped, and
the [E, C, D] buffer is processed with batched per-expert matmuls (einsum
on the expert-sharded axis — expert parallelism over the mesh 'model'
axis).  Static shapes throughout; drop rate is a benchmark metric.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import ModelConfig
from . import layers as L
from .transformer import init_cache  # same cache layout (GQA)

CAPACITY_FACTOR = 1.25

MOE_IMPL = "gather"   # "gather" (jit-level scatter) | "ep_a2a" (shard_map EP)


def set_moe_impl(impl: str) -> None:
    global MOE_IMPL
    MOE_IMPL = impl


def init_moe_mlp(key, cfg: ModelConfig, n: int) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": L.stack_init(ks[0], n, (d, e), scale=0.006),
        "wg": L.stack_init(ks[1], n, (e, d, f)),
        "wu": L.stack_init(ks[2], n, (e, d, f)),
        "wd": L.stack_init(ks[3], n, (e, f, d)),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp_stack(
            ks[4], n, d, cfg.n_shared_experts * cfg.d_ff_expert
        )
    return p


def moe_forward_ep(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

    Beyond-paper optimization (EXPERIMENTS.md §Perf): the jit-level scatter
    formulation makes the SPMD partitioner all-gather the full token set
    onto every expert shard (collective-dominated cells).  Here each device
    routes ONLY ITS OWN tokens to the owning expert shard along the 'model'
    axis — two all_to_alls of [T_local*K, D] replace per-layer full-token
    all-gathers (~model_axis x less ICI traffic).

    Per-device protocol (classic GShard EP, same machinery as the
    generation layer's `fetch_rows` shuffle):
      1. route:   top-k experts per local token; destination shard =
                  expert // E_local.
      2. a2a out: slot tokens into per-destination send buffers
                  (capacity-bounded, drops counted like `moe_forward`).
      3. compute: sort received tokens by local expert, batched per-expert
                  einsum [E_loc, C, D] x [E_loc, D, F].
      4. a2a back + weighted combine.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = L.get_mesh()
    assert mesh is not None and "model" in mesh.axis_names
    b, s, d = x.shape
    m = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // m
    dpa = L.dp_axes()

    def body(wr, wg, wu, wd, xb):
        # xb [b_loc, s_loc, D] — tokens of this device; experts e_loc mine
        bl, sl, _ = xb.shape
        tl = bl * sl
        xf = xb.reshape(tl, d)
        logits = (xf @ wr.astype(xf.dtype)).astype(jnp.float32)      # [Tl, E]
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        fe = topi.reshape(-1)                                        # [Tl*K]
        fw = topv.reshape(-1).astype(xf.dtype)
        ftok = jnp.arange(tl * k, dtype=jnp.int32) // k
        dest = fe // e_loc                                           # [Tl*K]
        cap = max(int(tl * k / m * 2.0) + 8, 8)
        order = jnp.argsort(dest)
        sd = dest[order]
        first = jnp.searchsorted(sd, sd, side="left")
        slot = jnp.arange(tl * k, dtype=jnp.int32) - first
        ok = slot < cap
        # overflow slots are pushed OUT OF BOUNDS so mode="drop" discards
        # them (clipping would overwrite a valid slot)
        slot_c = jnp.where(ok, slot, cap)
        send_x = jnp.zeros((m, cap, d), xf.dtype).at[sd, slot_c].set(
            xf[ftok[order]], mode="drop")
        send_e = jnp.zeros((m, cap), jnp.int32).at[sd, slot_c].set(
            fe[order] % e_loc, mode="drop")
        send_m = jnp.zeros((m, cap), xf.dtype).at[sd, slot_c].set(
            jnp.ones((), xf.dtype), mode="drop")
        a2a = lambda t: lax.all_to_all(t, "model", split_axis=0,
                                       concat_axis=0, tiled=True)
        rx = a2a(send_x).reshape(m * cap, d)      # tokens sent to my experts
        re_ = a2a(send_e).reshape(m * cap)
        rm = a2a(send_m).reshape(m * cap)
        # sort by local expert (invalid slots keyed AFTER all experts so the
        # sort key stays monotone — searchsorted needs a sorted array)
        c2 = max(int(m * cap / e_loc * 2.0) + 8, 8)
        key2 = re_ + (1 - rm.astype(jnp.int32)) * e_loc
        order2 = jnp.argsort(key2)
        sk2 = key2[order2]                           # sorted, invalid == e_loc
        first2 = jnp.searchsorted(sk2, sk2, side="left")
        slot2 = jnp.arange(m * cap, dtype=jnp.int32) - first2
        ok2 = jnp.logical_and(slot2 < c2, sk2 < e_loc)
        slot2c = jnp.where(ok2, slot2, c2)
        se2 = jnp.clip(sk2, 0, e_loc - 1)
        buf = jnp.zeros((e_loc, c2, d), xf.dtype).at[se2, slot2c].set(
            rx[order2], mode="drop")
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(xf.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu.astype(xf.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wd.astype(xf.dtype))
        # un-bucket back to recv order, a2a home, combine
        back = jnp.zeros((m * cap, d), xf.dtype).at[order2].set(
            out[se2, jnp.clip(slot2c, 0, c2 - 1)]
            * ok2.astype(xf.dtype)[:, None])
        home = a2a(back.reshape(m, cap, d)).reshape(m, cap, d)
        got = (home[sd, jnp.clip(slot_c, 0, cap - 1)]
               * ok.astype(xf.dtype)[:, None])        # sorted order
        contrib = jnp.zeros((tl * k, d), xf.dtype).at[order].set(got)
        y = jnp.zeros((tl, d), xf.dtype).at[ftok].add(
            contrib * fw[:, None])
        return y.reshape(bl, sl, d)

    y = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(dpa, "model", None)),
        out_specs=P(dpa, "model", None),
        check_vma=False,
    )(p["router"], p["wg"], p["wu"], p["wd"], x)
    if "shared" in p:
        y = y + L.mlp_forward(p["shared"], x.reshape(b * s, d)).reshape(b, s, d)
    return y


def moe_forward(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    mesh = L.get_mesh()
    if (MOE_IMPL == "ep_a2a" and mesh is not None
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0
            and x.shape[1] % mesh.shape["model"] == 0):
        return moe_forward_ep(p, x, cfg)
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    e = cfg.n_experts
    cap = max(int(t * k / e * CAPACITY_FACTOR), 1)
    xf = x.reshape(t, d)

    logits = (xf @ p["router"].astype(x.dtype)).astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)                                   # [T, K]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)                       # [T*K]
    flat_w = topv.reshape(-1)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - first
    keep = rank < cap
    rank_c = jnp.clip(rank, 0, cap - 1)
    src = xf[flat_tok[order]] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[se, rank_c].set(src, mode="drop")
    buf = L.shard(buf, "model", None, None)          # expert parallelism

    wg = p["wg"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(x.dtype))
    out = L.shard(out, "model", None, None)

    contrib = out[se, rank_c] * (flat_w[order] * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[flat_tok[order]].add(contrib)
    if "shared" in p:
        y = y + L.mlp_forward(p["shared"], xf)
    return y.reshape(b, s, d)


def moe_drop_rate(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Fraction of assignments dropped by capacity (benchmark metric)."""
    b, s, d = x.shape
    t = b * s
    cap = max(int(t * cfg.top_k / cfg.n_experts * CAPACITY_FACTOR), 1)
    logits = (x.reshape(t, d) @ p["router"].astype(x.dtype)).astype(jnp.float32)
    _, topi = lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    flat_e = topi.reshape(-1)
    se = jnp.sort(flat_e)
    rank = jnp.arange(t * cfg.top_k) - jnp.searchsorted(se, se, side="left")
    return (rank >= cap).mean()


# ------------------------------------------------------- qwen3-moe model --
def init_qwen3_moe(cfg: ModelConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 4)
    n = cfg.n_layers
    return {
        "embed": L.init_embed(ks[0], cfg),
        "layers": {
            "attn": L.init_attn_stack(ks[1], cfg, n),
            "moe": init_moe_mlp(ks[2], cfg, n),
            "ln1": jnp.ones((n, cfg.d_model), jnp.float32),
            "ln2": jnp.ones((n, cfg.d_model), jnp.float32),
        },
    }


def _block(cfg, x, layer, pos, cache=None, cache_pos=None):
    h, new_cache = L.attn_forward(
        layer["attn"], L.rmsnorm(layer["ln1"], x, cfg.norm_eps), cfg,
        pos=pos, cache=cache, cache_pos=cache_pos,
    )
    x = x + h
    x = x + moe_forward(layer["moe"], L.rmsnorm(layer["ln2"], x, cfg.norm_eps), cfg)
    return L.shard_batch(x), new_cache


def forward_train(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(x, layer):
        out, _ = _block(cfg, x, layer, pos)
        return out, None

    body = L.maybe_remat(body, cfg)
    x, _ = lax.scan(body, x, params["layers"])
    return L.lm_head(params["embed"], x, cfg)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    return L.lm_loss(forward_train(cfg, params, batch["tokens"]), batch["labels"])


def forward_decode(cfg, params, cache, tokens, pos):
    b = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens)
    qpos = jnp.broadcast_to(pos[None, None], (b, 1))

    def body(x, xs):
        layer, kc, vc = xs
        out, new_cache = _block(cfg, x, layer, qpos, cache=(kc, vc), cache_pos=pos)
        return out, new_cache

    x, (k_new, v_new) = lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    return L.lm_head(params["embed"], x, cfg)[:, 0], {"k": k_new, "v": v_new}
