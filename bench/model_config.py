"""Build the program's ``ModelConfig`` and ``EngineConfig`` from a
configuration file (``bench/configs/<name>.json``). Nothing is registered
in the program."""
from __future__ import annotations

import dataclasses


def model_config(conf: dict):
    from repro.configs.base import CHAIConfig, ModelConfig
    m = dict(conf["model"])
    chai = dict(m.pop("chai", {}))
    if "cluster_counts" in chai:
        chai["cluster_counts"] = tuple(chai["cluster_counts"])
    for key in ("ffn_types", "layer_types"):
        if key in m:
            m[key] = tuple(m[key])
    return ModelConfig(name=conf["name"], chai=CHAIConfig(**chai), **m)


def engine_config(conf: dict, **override):
    from repro.serving.engine import EngineConfig
    e = {k: v for k, v in conf["engine"].items() if k != "why"}
    e.update(override)
    names = {f.name for f in dataclasses.fields(EngineConfig)}
    unknown = sorted(set(e) - names)
    if unknown:
        raise KeyError(f"unknown engine settings {unknown}")
    return EngineConfig(**e)


def rehearsal_sizes(cfg):
    """A tiny copy of ``cfg`` for CPU rehearsal: same layer kinds, head
    layout (MHA or GQA) and FFN kinds, reduced widths, float32."""
    from repro.configs.base import reduced
    heads = 8 if cfg.is_mha else 16
    small = reduced(cfg, n_layers=min(cfg.n_layers, 3), d_model=8 * heads,
                    n_heads=heads, d_ff=96, vocab=512,
                    n_experts=8, top_k=2, moe_d_ff=32, dtype="float32")
    n_attn = small.n_attn_layers
    k = 5 if cfg.is_mha else 8
    return small.replace(chai=dataclasses.replace(
        small.chai, cluster_counts=(k,) * n_attn))
