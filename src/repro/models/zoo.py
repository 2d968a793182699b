"""Architecture zoo: uniform build/init/loss/decode API over all assigned
architectures + the GNN families (the paper's GCN, and GAT), plus the
sharding-spec rules that map any param/cache pytree onto the production
mesh.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import ModelConfig, ShapeConfig
from . import deepseek, gat, gcn, hybrid, moe, ssm, transformer, vlm, whisper
from . import layers as L


class ModelAPI(NamedTuple):
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    loss: Callable[[Any, Any], jax.Array]
    decode: Optional[Callable]        # (params, cache, tokens, pos) -> (logits, cache)
    init_cache: Optional[Callable]    # (batch, seq) -> cache
    logits: Optional[Callable] = None  # GNN: (params, SubgraphBatch) -> logits


_FAMILIES = {
    "dense": (transformer.init_lm, transformer.loss_fn,
              transformer.forward_decode, transformer.init_cache),
    "moe_qwen": (moe.init_qwen3_moe, moe.loss_fn,
                 moe.forward_decode, transformer.init_cache),
    "moe_deepseek": (deepseek.init_deepseek, deepseek.loss_fn,
                     deepseek.forward_decode, deepseek.init_cache),
    "ssm": (ssm.init_mamba2, ssm.loss_fn, ssm.forward_decode, ssm.init_cache),
    "hybrid": (hybrid.init_zamba2, hybrid.loss_fn,
               hybrid.forward_decode, hybrid.init_cache),
    "vlm": (vlm.init_vlm, vlm.loss_fn, vlm.forward_decode, vlm.init_cache),
    "audio": (whisper.init_whisper, whisper.loss_fn,
              whisper.forward_decode, whisper.init_cache),
}


def _family_key(cfg: ModelConfig) -> str:
    if cfg.family == "moe":
        return "moe_deepseek" if cfg.kv_lora_rank else "moe_qwen"
    return cfg.family


def build(cfg: ModelConfig) -> ModelAPI:
    """The family's functions bound to ``cfg``.  A GNN family gives
    ``init``, ``loss`` and ``logits`` over ``SubgraphBatch`` and no
    decode; each is looked up in its module when called."""
    if cfg.family == "gcn":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: gcn.init_gcn(cfg, key),
            loss=lambda p, b: gcn.gcn_loss(p, b),
            decode=None,
            init_cache=None,
            logits=lambda p, b: gcn.gcn_forward(p, b),
        )
    if cfg.family == "gat":
        return ModelAPI(
            cfg=cfg,
            init=lambda key: gat.init_gat(cfg, key),
            loss=lambda p, b: gat.gat_loss(p, b),
            decode=None,
            init_cache=None,
            logits=lambda p, b: gat.gat_forward(p, b),
        )
    init_f, loss_f, dec_f, cache_f = _FAMILIES[_family_key(cfg)]
    return ModelAPI(
        cfg=cfg,
        init=lambda key: init_f(cfg, key),
        loss=lambda p, b: loss_f(cfg, p, b),
        decode=lambda p, c, t, pos: dec_f(cfg, p, c, t, pos),
        init_cache=lambda batch, seq: cache_f(cfg, batch, seq),
    )


# ------------------------------------------------------------ input specs -
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (dry-run contract:
    weak-type-correct, shardable, no device allocation)."""
    s = jax.ShapeDtypeStruct
    b = shape.global_batch
    if shape.kind == "train":
        out = {
            "tokens": s((b, shape.seq_len), jnp.int32),
            "labels": s((b, shape.seq_len), jnp.int32),
        }
        if cfg.family == "vlm":
            out["vision"] = s((b, cfg.n_vision_tokens, cfg.d_vision), jnp.float32)
        if cfg.family == "audio":
            out["frames"] = s((b, cfg.n_audio_frames, cfg.d_audio), jnp.float32)
        return out
    # decode / prefill-as-decode: one new token against a seq_len cache
    return {"tokens": s((b, 1), jnp.int32)}


def prefill_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """prefill_* shapes lower the full-sequence forward (no labels)."""
    s = jax.ShapeDtypeStruct
    b = shape.global_batch
    out = {"tokens": s((b, shape.seq_len), jnp.int32)}
    if cfg.family == "vlm":
        out["vision"] = s((b, cfg.n_vision_tokens, cfg.d_vision), jnp.float32)
    if cfg.family == "audio":
        out["frames"] = s((b, cfg.n_audio_frames, cfg.d_audio), jnp.float32)
    return out


def forward_logits(cfg: ModelConfig, params, batch: dict) -> jax.Array:
    """Full-sequence forward (prefill).  Dispatches per family."""
    fam = _family_key(cfg)
    if fam == "dense":
        return transformer.forward_train(cfg, params, batch["tokens"])
    if fam == "moe_qwen":
        return moe.forward_train(cfg, params, batch["tokens"])
    if fam == "moe_deepseek":
        return deepseek.forward_train(cfg, params, batch["tokens"])
    if fam == "ssm":
        return ssm.forward_train(cfg, params, batch["tokens"])
    if fam == "hybrid":
        return hybrid.forward_train(cfg, params, batch["tokens"])
    if fam == "vlm":
        return vlm.forward_train(cfg, params, batch["tokens"], batch["vision"])
    if fam == "audio":
        return whisper.forward_train(cfg, params, batch["tokens"], batch["frames"])
    raise ValueError(fam)


# --------------------------------------------------------- sharding rules -
def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _dp_names(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _dp_size(mesh: Mesh) -> int:
    n = 1
    for a in _dp_names(mesh):
        n *= _axis_size(mesh, a)
    return n


def param_pspec(path: str, shape: tuple, mesh: Mesh, fsdp: bool = True) -> P:
    """Sharding rule for one parameter leaf.

    * expert stacks [L?, E, D, F] -> E over 'model' (expert parallelism),
      D over 'data' (FSDP).
    * matrices [..., in, out]     -> out over 'model' (tensor parallelism),
      in over 'data' (FSDP / ZeRO-3).
    * vectors (norms, gates)      -> replicated.
    Params never shard over 'pod' (cross-pod = pure data parallelism; the
    gradient AllReduce is the only DCN traffic)."""
    m, d = _axis_size(mesh, "model"), _axis_size(mesh, "data")
    dims = [None] * len(shape)
    if len(shape) < 2:
        return P(*dims)
    is_expert = any(k in path for k in ("wg", "wu", "wd")) and "moe" in path and len(shape) >= 3
    if is_expert and shape[-3] % m == 0:
        dims[-3] = "model"
        if fsdp and shape[-2] % d == 0:
            dims[-2] = "data"
        return P(*dims)
    if shape[-1] % m == 0:
        dims[-1] = "model"
    if fsdp and shape[-2] % d == 0:
        dims[-2] = "data"
    return P(*dims)


def param_pspecs(cfg: ModelConfig, params_shape, mesh: Mesh):
    """Map a (possibly abstract) param tree to PartitionSpecs."""
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    treedef = jax.tree.structure(params_shape)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        specs.append(param_pspec(pstr, leaf.shape, mesh, fsdp=cfg.fsdp_params))
    return jax.tree.unflatten(treedef, specs)


def cache_pspec(path: str, shape: tuple, mesh: Mesh, batch_axis: int) -> P:
    """KV/SSM-cache rule: shard batch over the dp axes when divisible;
    otherwise (long_500k, batch=1) shard the sequence axis over 'data'.
    The trailing feature axis shards over 'model' when divisible."""
    m = _axis_size(mesh, "model")
    dp = _dp_size(mesh)
    dims: list = [None] * len(shape)
    if shape[-1] % m == 0:
        dims[-1] = "model"
    if batch_axis < len(shape) and shape[batch_axis] % dp == 0 and shape[batch_axis] > 1:
        dims[batch_axis] = _dp_names(mesh)
    elif len(shape) >= 3:
        seq_axis = batch_axis + 1
        d = _axis_size(mesh, "data")
        if dims[seq_axis] is None and shape[seq_axis] % d == 0 and shape[seq_axis] >= d:
            dims[seq_axis] = "data"
    return P(*dims)


def cache_pspecs(cfg: ModelConfig, cache_shape, mesh: Mesh):
    flat = jax.tree_util.tree_flatten_with_path(cache_shape)[0]
    treedef = jax.tree.structure(cache_shape)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", k)) for k in path)
        batch_axis = 0 if pstr == "enc" else 1   # whisper enc cache is [B, T, D]
        specs.append(cache_pspec(pstr, leaf.shape, mesh, batch_axis))
    return jax.tree.unflatten(treedef, specs)


def batch_pspecs(cfg: ModelConfig, batch_shape, mesh: Mesh):
    """Inputs shard over the dp axes on their leading (batch) dim, unless
    batch == 1 (long_500k), which replicates."""
    dp = _dp_size(mesh)

    def one(leaf):
        dims = [None] * len(leaf.shape)
        if leaf.shape[0] % dp == 0 and leaf.shape[0] > 1:
            dims[0] = _dp_names(mesh)
        return P(*dims)

    return jax.tree.map(one, batch_shape)


def to_shardings(mesh: Mesh, pspecs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pspecs,
        is_leaf=lambda x: isinstance(x, P),
    )
