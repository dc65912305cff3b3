"""Model/arch configuration system.

Every assigned architecture is expressed as a ``ModelConfig``. Layer
heterogeneity (local/global attention, dense/MoE FFN, recurrent blocks) is
encoded as per-layer type strings so the transformer stack can build stacked
parameter groups and dispatch with ``lax.cond`` inside a scan.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence

# Layer mixer kinds.
ATTN_GLOBAL = "attn_global"
ATTN_LOCAL = "attn_local"   # sliding-window / local attention
RGLRU = "rglru"             # RecurrentGemma recurrent block
RWKV = "rwkv"               # RWKV-6 time-mix

# FFN kinds.
FFN_DENSE = "dense"
FFN_MOE = "moe"


@dataclass(frozen=True)
class CHAIConfig:
    """CHAI (Clustered Head Attention) configuration.

    ``cluster_counts`` is the offline elbow-selected number of clusters per
    layer (padded/stored per attention layer). ``k_max`` is the static compile
    width. ``warmup_tokens`` is the number of MHA decode steps observed before
    cluster-membership identification (paper: 5).
    """
    enabled: bool = False
    # Per-attention-layer cluster counts; if empty, derived by fraction.
    cluster_counts: tuple = ()
    # Fallback: fraction of query heads kept per layer if cluster_counts empty.
    cluster_fraction: float = 0.57
    warmup_tokens: int = 5
    kmeans_iters: int = 12
    # Feature window: how many trailing prefix positions feed clustering.
    feature_window: int = 256
    # 0 = paper behaviour (freeze after warmup); >0 = beyond-paper periodic
    # reclustering interval in decoded tokens.
    recluster_interval: int = 0
    # Ablation: also share V of the representative head (Table 4, CHAI-QKV).
    share_values: bool = False


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | audio | hybrid | ssm | vlm
    n_layers: int
    d_model: int
    n_heads: int                # query heads (0 => attention-free arch)
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 => d_model // n_heads
    layer_types: tuple = ()     # per-layer mixer kind; default all ATTN_GLOBAL
    ffn_types: tuple = ()       # per-layer FFN kind; default all FFN_DENSE
    window_size: int = 4096     # sliding window for ATTN_LOCAL
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0           # per-expert hidden dim
    capacity_factor: float = 1.25
    # --- activations / norms ---
    activation: str = "silu"    # silu | gelu | relu2
    gated_mlp: bool = True      # False => 2-matrix MLP (nemotron relu2)
    norm_eps: float = 1e-6
    # --- attention details ---
    rope_theta: float = 10000.0
    attn_logit_softcap: float = 0.0   # gemma2-style tanh softcap on scores
    final_logit_softcap: float = 0.0  # softcap on LM logits
    qk_norm: bool = False
    # --- recurrent (RG-LRU / RWKV) ---
    rnn_width: int = 0          # RG-LRU recurrent width (0 => d_model)
    conv_width: int = 4         # RecurrentGemma temporal conv width
    rwkv_head_dim: int = 64
    # --- frontend stub ---
    frontend: str = "none"      # none | audio | vision
    tie_embeddings: bool = False
    # --- KV cache quantization (beyond-paper perf knob, §Perf cell 3) ---
    # "" = model dtype; "int8" = per-(head,position) symmetric int8 for the
    # *global* K/V caches (decode is HBM-bound on cache reads: ~2x bytes).
    kv_cache_dtype: str = ""
    # --- CHAI ---
    chai: CHAIConfig = field(default_factory=CHAIConfig)
    # Attention flavour is derivable: full attention in every layer?
    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.layer_types:
            kind = RWKV if self.family == "ssm" else ATTN_GLOBAL
            object.__setattr__(self, "layer_types", (kind,) * self.n_layers)
        if not self.ffn_types:
            kind = FFN_MOE if self.n_experts > 0 else FFN_DENSE
            object.__setattr__(self, "ffn_types", (kind,) * self.n_layers)
        assert len(self.layer_types) == self.n_layers, self.name
        assert len(self.ffn_types) == self.n_layers, self.name
        if self.rnn_width == 0:
            object.__setattr__(self, "rnn_width", self.d_model)

    # ---- derived -----------------------------------------------------
    @property
    def attn_layer_ids(self):
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t in (ATTN_GLOBAL, ATTN_LOCAL))

    @property
    def n_attn_layers(self):
        return len(self.attn_layer_ids)

    @property
    def n_global_layers(self):
        return sum(1 for t in self.layer_types if t == ATTN_GLOBAL)

    @property
    def n_local_layers(self):
        return sum(1 for t in self.layer_types if t == ATTN_LOCAL)

    @property
    def n_rec_layers(self):
        return sum(1 for t in self.layer_types if t == RGLRU)

    @property
    def n_rwkv_layers(self):
        return sum(1 for t in self.layer_types if t == RWKV)

    @property
    def n_dense_ffn(self):
        return sum(1 for t in self.ffn_types if t == FFN_DENSE)

    @property
    def n_moe_ffn(self):
        return sum(1 for t in self.ffn_types if t == FFN_MOE)

    @property
    def n_rwkv_heads(self):
        return self.d_model // self.rwkv_head_dim

    @property
    def q_per_kv(self):
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def is_mha(self):
        """True when every query head has its own K/V (paper's setting)."""
        return self.n_heads > 0 and self.n_heads == self.n_kv_heads

    @property
    def sub_quadratic(self):
        """True if no layer needs an unbounded dense KV cache."""
        return all(t != ATTN_GLOBAL for t in self.layer_types)

    @property
    def supports_long_context(self):
        """long_500k eligibility: SSM / hybrid / sliding-window-major."""
        return self.family in ("ssm", "hybrid") or (
            self.n_local_layers > 0 or self.family == "dense" and False)

    def chai_cluster_counts(self):
        """Per-attention-layer cluster counts (static)."""
        import math
        n = self.n_attn_layers
        if n == 0:
            return ()
        if self.chai.cluster_counts:
            assert len(self.chai.cluster_counts) == n
            return tuple(self.chai.cluster_counts)
        # Fraction fallback, but never below n_kv_heads (GQA group floor) and
        # mimic the paper's depth profile: early layers keep more clusters.
        out = []
        for j in range(n):
            depth = j / max(n - 1, 1)
            frac = self.chai.cluster_fraction
            # paper: early layers high k (little redundancy), later layers low
            f = min(1.0, frac * (1.35 - 0.7 * depth))
            k = max(1, math.ceil(f * self.n_heads))
            if self.n_kv_heads > 1 and self.n_heads != self.n_kv_heads:
                k = max(k, self.n_kv_heads)  # block-diagonal GQA constraint
            out.append(min(k, self.n_heads))
        return tuple(out)

    @property
    def k_max(self):
        counts = self.chai_cluster_counts()
        return max(counts) if counts else 0

    def with_chai(self, **kw):
        return dataclasses.replace(self, chai=dataclasses.replace(self.chai, **kw))

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    def param_count(self):
        """Analytic parameter count N (embeddings included once)."""
        c = self
        n = c.vocab_size * c.d_model  # embed
        if not c.tie_embeddings:
            n += c.vocab_size * c.d_model
        qkv_out = c.n_heads * c.head_dim
        kv_out = c.n_kv_heads * c.head_dim
        attn = (c.d_model * qkv_out + 2 * c.d_model * kv_out
                + qkv_out * c.d_model)
        n_mats = 3 if c.gated_mlp else 2
        dense_ffn = n_mats * c.d_model * c.d_ff
        moe_ffn = (c.n_experts * 3 * c.d_model * c.moe_d_ff
                   + c.n_shared_experts * 3 * c.d_model * c.moe_d_ff
                   + c.d_model * c.n_experts)
        rg = 0
        if c.n_rec_layers:
            w = c.rnn_width
            rg = (2 * c.d_model * w + w * c.d_model + c.conv_width * w + 2 * w
                  + 2 * w)
        rwkv = 0
        if c.n_rwkv_layers:
            rwkv = 6 * c.d_model * c.d_model + 2 * c.d_model * c.d_ff
        for lt, ft in zip(c.layer_types, c.ffn_types):
            if lt in (ATTN_GLOBAL, ATTN_LOCAL):
                n += attn
            elif lt == RGLRU:
                n += rg
            elif lt == RWKV:
                n += rwkv
            if lt != RWKV:  # rwkv includes its own channel-mix as "ffn"
                n += dense_ffn if ft == FFN_DENSE else moe_ffn
            n += 2 * c.d_model  # norms
        return n

    def active_param_count(self):
        """Active params per token (MoE: only routed top-k + shared)."""
        c = self
        if c.n_moe_ffn == 0:
            return self.param_count()
        full = self.param_count()
        moe_total = c.n_moe_ffn * c.n_experts * 3 * c.d_model * c.moe_d_ff
        moe_active = c.n_moe_ffn * c.top_k * 3 * c.d_model * c.moe_d_ff
        return full - moe_total + moe_active


# ----------------------------------------------------------------------
# Shapes assigned to the LM-transformer pool.
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524288, 1, "decode"),
}

def reduced(cfg: ModelConfig, *, n_layers=None, d_model=64, n_heads=None,
            d_ff=128, vocab=256, window=16, n_experts=8, top_k=2,
            moe_d_ff=32, rnn_width=64, dtype="float32") -> ModelConfig:
    """Scaled-down same-family config for CPU smoke tests.

    Preserves the layer-type pattern (sliced/tiled to n_layers), GQA ratio,
    MoE-ness, frontend kind — everything structural."""
    if n_layers is None:
        n_layers = min(cfg.n_layers, 4)
    lt = (cfg.layer_types * n_layers)[:n_layers]
    # keep at least one of each kind present in the original
    kinds = list(dict.fromkeys(cfg.layer_types))
    lt = list(lt)
    for j, kind in enumerate(kinds):
        if kind not in lt and j < n_layers:
            lt[j] = kind
    ft = list((cfg.ffn_types * n_layers)[:n_layers])
    for kind in dict.fromkeys(cfg.ffn_types):
        if kind not in ft:
            ft[-1] = kind
    if n_heads is None:
        n_heads = max(4, min(8, cfg.n_heads)) if cfg.n_heads else 0
    n_kv = max(1, n_heads // max(cfg.q_per_kv, 1)) if cfg.n_heads else 0
    return dataclasses.replace(
        cfg,
        n_layers=n_layers,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=d_model // max(n_heads, 1) if n_heads else 0,
        d_ff=d_ff,
        vocab_size=vocab,
        layer_types=tuple(lt),
        ffn_types=tuple(ft),
        window_size=window,
        n_experts=n_experts if cfg.n_experts else 0,
        top_k=min(top_k, n_experts) if cfg.n_experts else 0,
        moe_d_ff=moe_d_ff if cfg.n_experts else 0,
        rnn_width=rnn_width if cfg.n_rec_layers else 0,
        rwkv_head_dim=16,
        dtype=dtype,
    )


def with_depth(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """The first ``n_layers`` layers of ``cfg`` at its published widths
    and dtype: the only cut a model this size needs to fit one chip.
    Every kept layer keeps its mixer / FFN kind and its CHAI cluster
    count (frozen from the full-depth profile, so a layer clusters the
    same way at any depth)."""
    if not 0 < n_layers <= cfg.n_layers:
        raise ValueError(f"{cfg.name} has {cfg.n_layers} layers; "
                         f"cannot keep {n_layers}")
    lt = cfg.layer_types[:n_layers]
    n_attn = sum(1 for t in lt if t in (ATTN_GLOBAL, ATTN_LOCAL))
    return dataclasses.replace(
        cfg, n_layers=n_layers, layer_types=lt,
        ffn_types=cfg.ffn_types[:n_layers],
        chai=dataclasses.replace(
            cfg.chai, cluster_counts=cfg.chai_cluster_counts()[:n_attn]))


_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from repro.configs import (  # noqa: F401
        nemotron_4_15b, gemma2_9b, gemma3_4b, h2o_danube_1_8b,
        qwen3_moe_30b_a3b, deepseek_moe_16b, musicgen_large,
        recurrentgemma_9b, rwkv6_1_6b, internvl2_76b, chai_llama_7b)
