"""Model registry: family -> implementation, plus analytic param counting."""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding.rules import Sharder


def build_model(cfg: ModelConfig, sharder: Optional[Sharder] = None):
    from repro.models.hubert import HuBERT
    from repro.models.transformer import TransformerLM
    from repro.models.xlstm import XLSTMModel
    from repro.models.zamba import ZambaModel

    if cfg.family == "audio":
        return HuBERT(cfg, sharder)
    if cfg.family in ("dense", "moe", "vlm"):
        return TransformerLM(cfg, sharder)
    if cfg.family == "ssm":
        return XLSTMModel(cfg, sharder)
    if cfg.family == "hybrid":
        return ZambaModel(cfg, sharder)
    raise ValueError(f"unknown family {cfg.family}")


def param_shapes_and_axes(cfg: ModelConfig):
    from repro.models.layers import abstract_init

    model = build_model(cfg)
    return abstract_init(model.init)


def param_count(cfg: ModelConfig) -> int:
    shapes, _ = param_shapes_and_axes(cfg)
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top-k of E experts + everything else)."""
    if cfg.num_experts == 0:
        return param_count(cfg)
    shapes, axes = param_shapes_and_axes(cfg)
    flat_s = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: x is None or (
        isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)))
    total = 0
    frac = cfg.experts_per_token / cfg.num_experts
    for (path, leaf), ax in zip(flat_s, flat_a):
        n = int(np.prod(leaf.shape))
        ax = ax or ()
        if "expert" in ax and "expert_in" in ax:  # per-expert weight
            n = int(n * frac)
        total += n
    return total


def model_flops_per_token(cfg: ModelConfig) -> float:
    """MODEL_FLOPS = 6*N(_active)*D convention (per token, fwd+bwd)."""
    return 6.0 * active_param_count(cfg)
