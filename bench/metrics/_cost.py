"""Operations and bytes of the algorithm, from the configuration's shapes.

These count what the mathematics needs, whatever implements it, so a
later kernel that does the same work faster raises its share of the
roofline and can never push it past 100%.
"""
from __future__ import annotations

from repro.core import cache as chai_cache

WARMUP, STEADY = chai_cache.PHASE_WARMUP, chai_cache.PHASE_STEADY


def kv_bytes_per_elem(cfg) -> float:
    return 1.0 if cfg.kv_cache_dtype == "int8" else 2.0


def k_rows(cfg, phase) -> int:
    """K rows one layer's decode attention reads for one token position:
    every KV head in WARMUP; in STEADY the representatives for MHA (the
    clustered cache) and every KV head for GQA (keys stay per group)."""
    if phase == STEADY and cfg.is_mha:
        return cfg.k_max
    return cfg.n_kv_heads


def qk_rows(cfg, phase) -> int:
    """Query rows scored against the keys: every head in WARMUP, the
    representatives in STEADY (k_max for MHA, KV x r_max for GQA)."""
    if phase != STEADY:
        return cfg.n_heads
    if cfg.is_mha:
        return cfg.k_max
    from repro.core.clustering import chai_widths
    return cfg.n_kv_heads * chai_widths(cfg)[1]


def v_rows(cfg) -> int:
    return cfg.n_kv_heads


def decode_attn(cfg, rows):
    """(flops, bytes) of decode attention over all layers for one step.

    ``rows``: [(phase, kv_len, cached_tokens, prefix id)] per slot that
    decoded. Only STEADY slots count (the clustered kernel's work). Bytes:
    each slot's private K and V rows over its own positions, and the rows
    of a shared prefix once per distinct prefix (the pages are shared)."""
    hd, n_att = cfg.head_dim, cfg.n_attn_layers
    eb = kv_bytes_per_elem(cfg)
    flops = byts = 0.0
    shared = {}
    for phase, n, cached, pre in rows:
        if phase != STEADY:
            continue
        kr, qr, vr = k_rows(cfg, phase), qk_rows(cfg, phase), v_rows(cfg)
        flops += 2.0 * n * hd * (qr + cfg.n_heads) * n_att
        own = n
        if pre >= 0 and cached > 0:
            own = n - cached
            shared[pre] = max(shared.get(pre, 0), cached)
        byts += own * hd * (kr + vr) * eb * n_att
        byts += (qr + cfg.n_heads) * hd * 2 * n_att      # q in, out back
    for cached in shared.values():
        byts += cached * hd * (k_rows(cfg, STEADY) + v_rows(cfg)) * eb * n_att
    return flops, byts


def matmul_flops_per_token(cfg) -> float:
    """Weight-matrix multiply-adds x 2 for one decoded token, at the active
    expert count, with the output head."""
    d, hd = cfg.d_model, cfg.head_dim
    attn = d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    dense = (3 if cfg.gated_mlp else 2) * d * cfg.d_ff
    moe = 0
    if cfg.n_experts:
        moe = (3 * d * cfg.moe_d_ff * (cfg.top_k + cfg.n_shared_experts)
               + d * cfg.n_experts)
    per_layer = [attn + (moe if f == "moe" else dense)
                 for f in cfg.ffn_types]
    return 2.0 * (sum(per_layer) + d * cfg.vocab_size)


def decode_step_flops(cfg, rows) -> float:
    """Model flops of one batched decode step: matmuls for every decoding
    slot plus attention at each slot's length (QK over the rows scored,
    AV over every head)."""
    mm = matmul_flops_per_token(cfg)
    att = 0.0
    for phase, n, _, _ in rows:
        att += 2.0 * n * cfg.head_dim * (qk_rows(cfg, phase)
                                         + cfg.n_heads) * cfg.n_attn_layers
    return mm * len(rows) + att
