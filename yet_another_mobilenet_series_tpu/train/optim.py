"""Optimizer construction (reference: utils/optim.py get_optimizer,
SURVEY.md §2 #7).

Reproduced semantics:
- TF-style RMSProp: accumulator initialized to 1.0, eps *inside* the sqrt,
  heavy-ball momentum applied after the RMS normalization — the combination
  the MNAS/MobileNet recipes assume (SURVEY.md §7 hard part 2). By default
  the momentum buffer also accumulates the LR-scaled update (TF ordering:
  ``mom = m*mom + lr*g/sqrt(nu+eps)``), which differs from torch-RMSprop's
  apply-time LR across every LR decay boundary; ``rmsprop_tf_momentum_order
  = false`` selects the torch ordering.
- Coupled L2 weight decay added to the *gradient* before the optimizer
  transform (torch ``weight_decay=`` semantics, not AdamW-decoupled).
- Per-parameter weight-decay exemptions: BN gamma/beta and biases (and
  optionally depthwise kernels) get no decay.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..config import OptimConfig


def clip_by_global_norm(max_norm: float, psum_axis: str | None = None) -> optax.GradientTransformation:
    """optax.clip_by_global_norm, but norm-aware of cross-replica sharding:
    with ``psum_axis`` the squared norm is psum'd so that clipping a ZeRO
    gradient SHARD uses the true global norm (each replica computes the same
    scale, so shards stay consistent). Same (empty) state as optax's — the
    optimizer state tree is checkpoint-compatible either way."""

    def init(params):
        del params
        return optax.EmptyState()

    def update(updates, state, params=None):
        del params
        sq = optax.global_norm(updates) ** 2
        if psum_axis is not None:
            sq = lax.psum(sq, psum_axis)
        norm = jnp.sqrt(sq)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-16))
        return jax.tree.map(lambda u: u * scale, updates), state

    return optax.GradientTransformation(init, update)


def wd_mask(params, cfg: OptimConfig):
    """True = apply weight decay. Walks the param tree by key names:
    BN params live under '*_bn'/'bn' subtrees with leaves gamma/beta; biases
    are leaves named 'b'; depthwise kernels live under 'dw*' subtrees."""

    def mask_tree(tree, path=()):
        if isinstance(tree, dict):
            return {k: mask_tree(v, path + (k,)) for k, v in tree.items()}
        leaf_name = path[-1] if path else ""
        in_bn = any(p == "bn" or p.endswith("_bn") for p in path)
        in_dw = any(p.startswith("dw") and not p.endswith("_bn") for p in path)
        if cfg.wd_skip_bn and (in_bn or leaf_name in ("gamma", "beta")):
            return False
        if cfg.wd_skip_bias and leaf_name == "b":
            return False
        if cfg.wd_skip_depthwise and in_dw:
            return False
        return True

    return mask_tree(params)


def make_optimizer(
    cfg: OptimConfig, lr_fn: Callable, params_example, *, shard_axis: str | None = None
) -> optax.GradientTransformation:
    """``shard_axis``: set to the mesh axis name when the optimizer will run
    on ZeRO gradient shards (dist.shard_optimizer) so grad clipping psums the
    true global norm instead of clipping per-shard."""
    txs = []
    if cfg.grad_clip_norm > 0:
        txs.append(clip_by_global_norm(cfg.grad_clip_norm, psum_axis=shard_axis))
    if cfg.weight_decay > 0:
        mask = wd_mask(params_example, cfg)
        txs.append(optax.add_decayed_weights(cfg.weight_decay, mask=lambda p: mask))
    lr_applied = False
    if cfg.optimizer == "rmsprop":
        # TF-style: nu0=1, update = g / sqrt(nu + eps); then momentum.
        txs.append(optax.scale_by_rms(decay=cfg.rmsprop_decay, eps=cfg.rmsprop_eps, initial_scale=1.0))
        if cfg.momentum > 0:
            if cfg.rmsprop_tf_momentum_order:
                # TF ordering: mom = m*mom + lr*g/sqrt(nu+eps) — LR scales the
                # normalized gradient BEFORE it enters the buffer, so earlier
                # contributions keep the LR of the step that produced them.
                txs.append(optax.scale_by_learning_rate(lr_fn))
                lr_applied = True
            txs.append(optax.trace(decay=cfg.momentum, nesterov=False))
    elif cfg.optimizer == "sgd":
        if cfg.momentum > 0:
            # torch SGD semantics: buf = m*buf + g; param -= lr*buf.
            txs.append(optax.trace(decay=cfg.momentum, nesterov=False))
    elif cfg.optimizer == "adamw":
        # decoupled variant kept for experimentation; wd handled above stays
        # coupled unless weight_decay==0 here.
        txs.append(optax.scale_by_adam(b1=cfg.adam_b1, b2=cfg.adam_b2))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    if not lr_applied:
        txs.append(optax.scale_by_learning_rate(lr_fn))
    return optax.chain(*txs)
