"""Configuration dataclasses for the repro framework.

One ``ModelConfig`` describes any architecture in the zoo (the paper's GCN
plus the 10 assigned LM-family architectures).  ``ShapeConfig`` describes an
input-shape cell (train / prefill / decode / long-decode).  ``MeshConfig``
describes the device mesh.  All are plain frozen dataclasses — no jax state
is touched at import time.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

#: cache associativities the probe/insert paths implement, the cache
#: placement modes, and the shard-probe wire formats — defined HERE
#: (jax-free) so ModelConfig validation and core/feature_cache.py (which
#: imports jax) share one source of truth
VALID_CACHE_ASSOC = (1, 2, 4)
VALID_CACHE_MODES = ("replicated", "sharded", "tiered")
VALID_CACHE_WIRES = ("dense", "compact")
#: where the authoritative feature table lives: "device" row-shards it
#: over the workers (the owner fetch resolves misses on-device);
#: "host" keeps it in host RAM behind the L3 store (misses resolve via
#: an async double-buffered host gather — core/host_store.py)
VALID_FEATURE_STORES = ("device", "host")
#: the graph neural network families: trained by ``train_gcn`` on
#: GraphGen+ subgraph batches (``ModelConfig.is_gnn``), not on tokens
GNN_FAMILIES = ("gcn", "gat")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | vlm | audio | ssm | hybrid | gcn | gat
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0          # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0       # per-expert FFN width (qwen3/deepseek style)
    first_dense_layers: int = 0  # deepseek: first k layers are dense
    # --- MLA (deepseek) ---
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 128
    conv_width: int = 4
    # --- hybrid (zamba2): shared attention block every `attn_every` layers ---
    attn_every: int = 0
    # --- vlm: cross-attention every `cross_attn_every` layers ---
    cross_attn_every: int = 0
    n_vision_tokens: int = 0
    d_vision: int = 0
    # --- audio (whisper): encoder-decoder ---
    n_encoder_layers: int = 0
    n_audio_frames: int = 0
    d_audio: int = 0
    # --- gcn (the paper's model) and gat: GNN_FAMILIES ---
    gcn_hidden: int = 0
    gcn_in_dim: int = 0
    n_classes: int = 0
    fanouts: Tuple[int, ...] = ()
    gat_heads: int = 0         # gat: attention heads; each is
                               # gcn_hidden // gat_heads wide
    # --- distributed feature-fetch policy (generation step 4) ---
    cache_rows: int = 0        # hot-node feature cache slots per worker
                               # (rounded UP to a power of two at
                               # construction; 0 disables the cache tier)
    cache_admit: int = 2       # misses before a candidate id is admitted
    cache_assoc: int = 1       # ways per cache set (1 = direct-mapped;
                               # 2/4-way recovers slot-collision losses)
    cache_mode: str = "replicated"
                               # "replicated": each worker caches its own
                               # stream (PR 2 behavior, the single-worker
                               # default); "sharded": the cache id-space
                               # partitions across workers and misses are
                               # first routed to their cache-shard holder
                               # (effective capacity x W); "tiered": a
                               # small replicated L1 (the global Zipf head,
                               # probed with zero network traffic) in front
                               # of the sharded L2 — L1 misses take the
                               # shard-probe round, shard misses fall
                               # through to the owner fetch
    cache_l1_rows: int = 0     # tiered mode: replicated L1 slots per worker
                               # (rounded UP to a power of two; 0 = auto,
                               # cache_rows // 8 — the "~1/8 head" default)
    cache_l1_promote: int = 3  # tiered mode: times the L2 tier must serve
                               # a row to this worker before the row is
                               # promoted into its L1 — the frequency
                               # threshold that migrates the hottest rows
                               # to every worker without a broadcast
    cache_wire: str = "compact"
                               # shard-probe response wire format (sharded/
                               # tiered modes, W > 1): "dense" ships the
                               # full [W, cap, D] row block back even
                               # though only hit slots carry data;
                               # "compact" ships a packed hit bitmap plus
                               # a row payload bounded by cache_hit_cap —
                               # stage-1 bytes then scale with hits, not
                               # with the probe capacity
    cache_hit_cap: int = 0     # compact wire: per-destination row-payload
                               # slots of the probe response; 0 = auto
                               # (half the probe capacity; launch/train.py
                               # calibrates a tighter bound from observed
                               # hit peaks, with a dense-fallback rung)
    capacity_slack: Optional[float] = None
                               # per-destination shuffle capacity slack;
                               # None = launcher auto-sizes from n_dropped
                               # (dryrun compiles at the 2.0 default)
    feature_store: str = "device"
                               # where the feature table lives: "device"
                               # row-shards it over the workers (misses
                               # pay the routed owner fetch); "host"
                               # keeps it in host RAM as the L3 tier —
                               # cache misses are staged and resolved by
                               # an async host gather double-buffered
                               # with the next step's compute
    host_gather_depth: int = 2 # host store pipeline depth: 2 issues the
                               # gather on a worker thread so the
                               # device_put overlaps the compute step;
                               # 1 gathers synchronously (overlap off —
                               # the benchmark's comparison column)
    # --- performance knobs (hillclimbed in §Perf) ---
    remat: str = "none"        # none | full | dots
    scan_layers: bool = True   # stack layer params and lax.scan over them
    use_flash_attention: bool = False
    fsdp_params: bool = True   # shard params over the data axis (ZeRO-3 style)

    def __post_init__(self):
        # validate the cache policy at CONSTRUCTION, not at trace time: a
        # non-power-of-two cache_rows used to surface as a ValueError deep
        # inside the jitted fetch (hash_slots), long after the config was
        # built.  Round up — the caller asked for at least that many slots.
        if self.cache_rows < 0:
            raise ValueError(f"cache_rows must be >= 0, got {self.cache_rows}")
        if self.cache_rows and self.cache_rows & (self.cache_rows - 1):
            object.__setattr__(self, "cache_rows",
                               1 << self.cache_rows.bit_length())
        if self.cache_assoc not in VALID_CACHE_ASSOC:
            raise ValueError(
                f"cache_assoc must be one of {VALID_CACHE_ASSOC}, "
                f"got {self.cache_assoc}")
        if self.cache_rows and self.cache_assoc > self.cache_rows:
            raise ValueError(
                f"cache_assoc {self.cache_assoc} exceeds cache_rows "
                f"{self.cache_rows}")
        if self.cache_mode not in VALID_CACHE_MODES:
            raise ValueError(
                f"cache_mode must be one of {VALID_CACHE_MODES}, "
                f"got {self.cache_mode!r}")
        if self.cache_l1_rows < 0:
            raise ValueError(
                f"cache_l1_rows must be >= 0, got {self.cache_l1_rows}")
        if self.cache_l1_rows and self.cache_l1_rows & (self.cache_l1_rows - 1):
            object.__setattr__(self, "cache_l1_rows",
                               1 << self.cache_l1_rows.bit_length())
        if self.cache_l1_promote < 1:
            raise ValueError(
                f"cache_l1_promote must be >= 1, got {self.cache_l1_promote}")
        if self.cache_wire not in VALID_CACHE_WIRES:
            raise ValueError(
                f"cache_wire must be one of {VALID_CACHE_WIRES}, "
                f"got {self.cache_wire!r}")
        if self.cache_hit_cap < 0:
            raise ValueError(
                f"cache_hit_cap must be >= 0 (0 = auto), "
                f"got {self.cache_hit_cap}")
        if self.feature_store not in VALID_FEATURE_STORES:
            raise ValueError(
                f"feature_store must be one of {VALID_FEATURE_STORES}, "
                f"got {self.feature_store!r}")
        if self.family == "gat" and (
                self.gat_heads < 1 or self.gcn_hidden % self.gat_heads):
            raise ValueError(
                f"gat needs gat_heads >= 1 dividing gcn_hidden "
                f"{self.gcn_hidden}, got {self.gat_heads}")
        if self.host_gather_depth not in (1, 2):
            raise ValueError(
                f"host_gather_depth must be 1 (synchronous) or 2 "
                f"(double-buffered), got {self.host_gather_depth}")
        # deliberately NO cross-field mode check here: launchers override
        # one field at a time with dataclasses.replace, so a tiered arch
        # config being switched to --cache-mode sharded must not trip over
        # its (now ignored) cache_l1_rows — CacheConfig.from_model simply
        # drops the L1 knobs outside tiered mode, and the strict check
        # lives in CacheConfig.validated() where the policy is final

    def with_candidate(self, cand: "TuneCandidate") -> "ModelConfig":
        """Self with an autotuner ``TuneCandidate`` applied — the config
        re-jit seam.

        Returns a new ``ModelConfig`` whose generation knobs (fanouts,
        cache sizes, associativity, hit cap, capacity slack) are replaced
        by the candidate's; everything else (model dims, placement mode,
        wire format, feature store) is untouched.  ``__post_init__``
        re-validates, so an infeasible candidate raises here — before
        anything is compiled against it.  The launcher rebuilds
        ``CacheConfig.from_model`` + the generator from the result;
        nothing downstream knows the config came from a search."""
        return dataclasses.replace(
            self, fanouts=tuple(cand.fanouts), cache_rows=cand.cache_rows,
            cache_l1_rows=cand.l1_rows, cache_assoc=cand.assoc,
            cache_hit_cap=cand.hit_cap, capacity_slack=cand.capacity_slack)

    @property
    def is_gnn(self) -> bool:
        """Whether the family is a graph neural network (``GNN_FAMILIES``),
        trained and served on subgraph batches."""
        return self.family in GNN_FAMILIES

    @property
    def resolved_head_dim(self) -> int:
        """Per-head attention dim: ``head_dim`` when set explicitly,
        else ``d_model // n_heads``."""
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND roofline, not allocation)."""
        if self.family == "gcn":
            d, h = self.gcn_in_dim, self.gcn_hidden
            depth = max(len(self.fanouts), 1)
            # per conv layer: self + neighbor transforms + bias
            total = 2 * d * h + h + (depth - 1) * (2 * h * h + h)
            return total + h * self.n_classes + self.n_classes
        if self.family == "gat":
            d, h = self.gcn_in_dim, self.gcn_hidden
            depth = max(len(self.fanouts), 1)
            # per layer: W, a_src and a_dst (h each), bias
            total = d * h + 3 * h + (depth - 1) * (h * h + 3 * h)
            return total + h * self.n_classes + self.n_classes
        hd = self.resolved_head_dim
        emb = self.vocab_size * self.d_model
        out = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        if self.family == "ssm":
            per = _mamba2_layer_params(self)
            return emb + out + self.n_layers * per
        if self.family == "hybrid":
            n_attn = self.n_layers // max(self.attn_every, 1)
            per = _mamba2_layer_params(self)
            attn = _attn_params(self) + _mlp_params(self.d_model, self.d_ff)
            return emb + out + self.n_layers * per + attn + (n_attn - 1) * 0
        per = _attn_params(self)
        if self.family == "moe":
            dense_ff = _mlp_params(self.d_model, self.d_ff if self.d_ff else self.d_ff_expert)
            moe_ff = (
                self.n_experts * _mlp_params(self.d_model, self.d_ff_expert)
                + self.n_shared_experts * _mlp_params(self.d_model, self.d_ff_expert)
                + self.d_model * self.n_experts
            )
            n_moe = self.n_layers - self.first_dense_layers
            ff_total = self.first_dense_layers * dense_ff + n_moe * moe_ff
        else:
            ff_total = self.n_layers * _mlp_params(self.d_model, self.d_ff)
        total = emb + out + self.n_layers * per + ff_total
        if self.family == "audio":
            total += self.n_encoder_layers * (
                _attn_params(self) + _mlp_params(self.d_model, self.d_ff)
            )
            total += self.n_layers * _attn_params(self)  # decoder cross-attn
        if self.family == "vlm":
            n_cross = self.n_layers // max(self.cross_attn_every, 1)
            total += n_cross * _attn_params(self)
        return total

    def active_param_count(self) -> int:
        """Active params per token (== param_count for dense)."""
        if self.family != "moe":
            return self.param_count()
        per_attn = _attn_params(self)
        emb = self.vocab_size * self.d_model
        out = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        active_ff = (self.top_k + self.n_shared_experts) * _mlp_params(
            self.d_model, self.d_ff_expert
        ) + self.d_model * self.n_experts
        dense_ff = _mlp_params(self.d_model, self.d_ff if self.d_ff else self.d_ff_expert)
        n_moe = self.n_layers - self.first_dense_layers
        return (
            emb + out + self.n_layers * per_attn
            + self.first_dense_layers * dense_ff + n_moe * active_ff
        )


def _attn_params(cfg: ModelConfig) -> int:
    hd = cfg.resolved_head_dim
    if cfg.kv_lora_rank:  # MLA
        q = cfg.d_model * cfg.n_heads * (cfg.qk_rope_head_dim + cfg.qk_nope_head_dim)
        kv = (
            cfg.d_model * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        )
        o = cfg.n_heads * cfg.v_head_dim * cfg.d_model
        return q + kv + o
    q = cfg.d_model * cfg.n_heads * hd
    kv = 2 * cfg.d_model * cfg.n_kv_heads * hd
    o = cfg.n_heads * hd * cfg.d_model
    return q + kv + o


def _mlp_params(d_model: int, d_ff: int) -> int:
    return 3 * d_model * d_ff  # gate, up, down


def _mamba2_layer_params(cfg: ModelConfig) -> int:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = cfg.ssm_heads or max(d_in // max(cfg.ssm_head_dim, 1), 1)
    in_proj = cfg.d_model * (2 * d_in + 2 * cfg.ssm_state + nh)
    conv = (d_in + 2 * cfg.ssm_state) * cfg.conv_width
    out_proj = d_in * cfg.d_model
    return in_proj + conv + out_proj + 2 * nh


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str            # train_4k | prefill_32k | decode_32k | long_500k
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        """Device-grid shape: 16x16 per pod, with a leading pod axis of 2
        when ``multi_pod``."""
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        """Mesh axis names, matching ``launch/mesh.py``'s production
        tuples — ``(pod,) + (data, model)``."""
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def n_devices(self) -> int:
        """Total devices in the mesh (product of ``shape``)."""
        n = 1
        for s in self.shape:
            n *= s
        return n


# TPU v5e hardware constants for the roofline model (target hardware).
PEAK_FLOPS_BF16 = 197e12        # per chip, FLOP/s
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
PCIE_BW = 16e9                  # bytes/s host<->device (PCIe gen4 x16) —
                                # the L3 host-gather term of the roofline


class TuneCandidate(NamedTuple):
    """One point of the autotuner's joint search space (jax-free).

    The knobs the profile-driven autotuner (``launch/autotune.py``)
    searches jointly against its trace-fit cost model: the per-hop
    fanout shape, the cache-tier sizes, the set associativity, the
    compact-wire payload bound, and the exchange capacity slack.  A
    candidate is pure data — applying one to a ``ModelConfig``
    (``ModelConfig.with_candidate``) or a ``CacheConfig`` is THE re-jit
    seam: the launcher rebuilds the generator from the replaced config,
    nothing else changes.
    """
    fanouts: Tuple[int, ...]    # per-hop fanout shape (workload-defining:
                                # the default grid pins it to the config's)
    cache_rows: int             # main-tier (L2) cache slots per worker
    l1_rows: int                # tiered mode: replicated L1 slots (0 else)
    assoc: int                  # cache ways per set, in VALID_CACHE_ASSOC
    hit_cap: int                # compact-wire payload bound (0 = auto)
    capacity_slack: float       # exchange-capacity slack factor


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 1000
    microbatches: int = 1       # gradient accumulation
    grad_sync: str = "psum"     # psum | tree (explicit butterfly tree reduction)
    compress_grads: bool = False
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
