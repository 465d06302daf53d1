"""Train step primitives: tree-training and baseline modes behind one
interface.

``make_train_step(cfg, opt_cfg, impl)`` returns a jit-able
``(params, opt_state, batch) → (params, opt_state, metrics)``.  Whether a
step is "tree" or "baseline" is decided purely by how the batch was packed
(core/packing.pack_trees vs pack_linear_paths) — the model code is shared,
which is what makes the speedup comparison apples-to-apples.

The production trainer composes these pieces differently: the unified
plan→execute engine (train/engine.py) accumulates per-microbatch grads
on-device and applies ``jitted_update`` — the AdamW executable cached per
OptimizerConfig below.
"""
from __future__ import annotations

from functools import lru_cache

import jax

from repro.configs.base import ModelConfig
from repro.models.model import loss_and_metrics
from repro.train.optimizer import OptimizerConfig, adamw_update


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    impl: str = "ref", donate: bool = True):
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_and_metrics(cfg, p, batch, impl), has_aux=True)(
                params)
        params, opt_state, opt_metrics = adamw_update(
            opt_cfg, params, grads, opt_state)
        return params, opt_state, {**metrics, **opt_metrics, "total": loss}

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_grad_fn(cfg: ModelConfig, impl: str = "ref"):
    """Gradient-only fn (for accumulation / partitioned drivers)."""
    def gfn(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_and_metrics(cfg, p, batch, impl),
            has_aux=True)(params)
        return loss, grads, metrics

    return jax.jit(gfn)


@lru_cache(maxsize=16)
def jitted_update(opt_cfg: OptimizerConfig, donate: bool = False):
    """The jitted AdamW update, cached per (OptimizerConfig, donate) —
    tracing once instead of on every call.  ``donate=True`` donates
    (params, grads, opt_state) for in-place buffer reuse; this is the
    cache the unified engine (train/engine.py) uses too.

    Signature of the returned fn: ``(params, grads, opt_state) →
    (new_params, new_opt_state, metrics)``."""
    def update(params, grads, opt_state):
        with jax.named_scope("adamw"):
            return adamw_update(opt_cfg, params, grads, opt_state)

    return jax.jit(update, donate_argnums=(0, 1, 2) if donate else ())


def apply_grads(opt_cfg: OptimizerConfig, params, opt_state, grads):
    return jitted_update(opt_cfg)(params, grads, opt_state)
