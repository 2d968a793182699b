"""Config registry: ``get_config(arch_id)`` + reduced smoke variants."""
from __future__ import annotations

import dataclasses

from ..core.config import ModelConfig, SHAPES, ShapeConfig
from . import (
    deepseek_v2_236b,
    graphgen_gat,
    graphgen_gcn,
    graphgen_gcn_deep,
    graphgen_sage,
    llama32_vision_11b,
    llama3_405b,
    mamba2_1p3b,
    qwen3_moe_30b_a3b,
    smollm_135m,
    smollm_360m,
    stablelm_12b,
    whisper_small,
    zamba2_1p2b,
)

REGISTRY: dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (
        smollm_135m, smollm_360m, stablelm_12b, llama3_405b,
        qwen3_moe_30b_a3b, deepseek_v2_236b, llama32_vision_11b,
        whisper_small, mamba2_1p3b, zamba2_1p2b, graphgen_gcn,
        graphgen_sage, graphgen_gcn_deep, graphgen_gat,
    )
}

ASSIGNED_ARCHS = [n for n, c in REGISTRY.items() if not c.is_gnn]

# archs whose attention is quadratic-only: long_500k is skipped for them
# (DESIGN.md §4); SSM/hybrid run it.
SUBQUADRATIC = {"mamba2-1.3b", "zamba2-1.2b"}


def get_config(name: str) -> ModelConfig:
    return REGISTRY[name]


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    if cfg.is_gnn:
        # shrink fanouts but keep the configured sampling depth; keep the
        # cache tier on (tiny) when the full config enables it, and a GAT
        # multi-headed
        depth = max(len(cfg.fanouts), 1)
        small = ((4, 3) + (2,) * depth)[:depth]
        return dataclasses.replace(cfg, gcn_in_dim=16, gcn_hidden=32, n_classes=5,
                                   fanouts=small,
                                   gat_heads=min(cfg.gat_heads, 2),
                                   cache_rows=min(cfg.cache_rows, 256),
                                   cache_l1_rows=min(cfg.cache_l1_rows, 32))
    hd = 16
    heads = max(cfg.n_heads // 4, 2) if cfg.n_heads else 0
    kv = max(cfg.n_kv_heads // 4, 1) if cfg.n_kv_heads else 0
    kv = min(kv, heads) if heads else 0
    if heads and kv and heads % kv:
        kv = 1
    rep = {
        "n_layers": min(cfg.n_layers, 4),
        "d_model": 64,
        "n_heads": heads,
        "n_kv_heads": kv,
        "head_dim": hd if heads else 0,
        "d_ff": 128 if cfg.d_ff else 0,
        "vocab_size": 512,
        "remat": "none",
    }
    if cfg.family == "moe":
        rep.update(n_experts=8, top_k=2, d_ff_expert=32)
        if cfg.kv_lora_rank:
            rep.update(kv_lora_rank=24, q_lora_rank=32, qk_rope_head_dim=8,
                       qk_nope_head_dim=16, v_head_dim=16, first_dense_layers=1,
                       n_layers=3, n_shared_experts=1, d_ff=64)
    if cfg.family in ("ssm", "hybrid"):
        rep.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
    if cfg.family == "hybrid":
        rep.update(n_layers=5, attn_every=2)
    if cfg.family == "vlm":
        rep.update(n_layers=4, cross_attn_every=2, n_vision_tokens=8, d_vision=24)
    if cfg.family == "audio":
        rep.update(n_encoder_layers=2, n_layers=2, n_audio_frames=12, d_audio=24)
    return dataclasses.replace(cfg, **rep)


def smoke_shape(kind: str = "train") -> ShapeConfig:
    if kind == "train":
        return ShapeConfig("smoke_train", "train", 32, 4)
    return ShapeConfig("smoke_decode", "decode", 32, 4)
