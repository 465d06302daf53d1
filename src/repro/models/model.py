"""Public model API: init / loss / forward + host-side batch preparation."""
from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packing import TreeBatch
from repro.models.layers import prev_powers
from repro.models.transformer import (forward, init_params, layer_groups,
                                      loss_and_metrics)

__all__ = ["init_params", "forward", "loss_and_metrics", "prepare_batch",
           "needs_chunks", "max_conv_taps", "layer_groups",
           "has_router_loss"]


def has_router_loss(cfg: ModelConfig) -> bool:
    """Whether the model takes the per-branch ``aux_weight`` column (its
    router loss is summed per branch, ``models/moe.router_loss``)."""
    return cfg.moe is not None


def needs_chunks(cfg: ModelConfig) -> bool:
    return cfg.ssm is not None


def max_conv_taps(cfg: ModelConfig) -> int:
    """How many path-predecessor gathers the model needs (conv K−1)."""
    if cfg.ssm is None:
        return 0
    if cfg.ssm.kind == "rwkv6":
        return 1                      # token shift only (uses prev_idx)
    return cfg.ssm.conv_kernel - 1


def prepare_batch(cfg: ModelConfig, tb: TreeBatch,
                  extra_embeds: Optional[np.ndarray] = None, *,
                  num_trees: Optional[int] = None) -> dict:
    """TreeBatch (host numpy) → jnp input dict for forward/loss.

    ``num_trees`` overrides the loss normalizer (mean over trees): when a
    step trains more trees than the packed batch holds — oversized trees
    riding the partition waves — the packed loss must divide by the
    step's FULL tree count so both shares sum to one mean-over-trees
    objective."""
    d: dict[str, Any] = {
        "tokens": jnp.asarray(tb.tokens),
        "pos_ids": jnp.asarray(tb.pos_ids),
        "kv_last": jnp.asarray(tb.kv_last),
        "weight": jnp.asarray(tb.weight),
        "prev_idx": jnp.asarray(tb.prev_idx),
        "valid": jnp.asarray(tb.valid),
        "num_trees": tb.num_trees if num_trees is None else num_trees,
    }
    if has_router_loss(cfg):
        d["aux_weight"] = jnp.asarray(tb.aux_weight)
    if needs_chunks(cfg):
        assert tb.chunk_parent is not None, \
            f"{cfg.name} needs chunk-aligned serialization (SSM family)"
        d["chunk_parent"] = jnp.asarray(tb.chunk_parent)
        k = max(1, max_conv_taps(cfg))
        d["prev_pows"] = jnp.asarray(prev_powers(tb.prev_idx, k))
    if extra_embeds is not None:
        d["extra_embeds"] = jnp.asarray(extra_embeds)
    elif tb.extra_embeds is not None:
        d["extra_embeds"] = jnp.asarray(tb.extra_embeds)
    elif cfg.frontend is not None:
        # stub frontend: zeros of the configured prefix length
        B = tb.tokens.shape[0]
        d["extra_embeds"] = jnp.zeros(
            (B, cfg.frontend_len, cfg.d_model),
            jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    return d
