"""In-jit dynamic shrinkage via channel masks (SURVEY.md §3.2 TPU translation).

The reference physically rebuilds the network with fewer channels every K
steps — hostile to XLA's static shapes. Here shrinkage is a monotonic 0/1
mask over each block's expanded channels, updated *inside* jit at a fixed
cadence; masked forward == physically shrunk forward exactly (proven in
tests/test_ops.py and test_nas.py). Physical rematerialization happens at a
much coarser cadence (nas/rematerialize.py) to reclaim real FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..config import PruneConfig
from ..models.specs import Network
from ..utils.profiling import masked_macs


def prunable_blocks(net: Network) -> list[int]:
    """Blocks whose expanded channels are atoms. Blocks WITHOUT an expand conv
    (t=1 / depthwise-separable) are excluded: their depthwise channels are the
    block's input itself, so removing one cannot be rematerialized into a
    smaller dense block (the kept channels would be a non-contiguous gather of
    the input)."""
    return [i for i, b in enumerate(net.blocks) if b.has_expand]


def init_masks(net: Network) -> dict[str, jax.Array]:
    """All-alive masks for every prunable block (string block-index keys,
    matching the params tree convention)."""
    return {str(i): jnp.ones((net.blocks[i].expanded_channels,), jnp.float32) for i in prunable_blocks(net)}


def make_mask_update(net: Network, cfg: PruneConfig):
    """Returns update(params, masks) -> new_masks, jit-compatible.

    An atom dies when |gamma| < threshold; death is irreversible (mask is
    multiplied in), matching the reference's one-way shrinkage.
    """
    threshold = float(cfg.gamma_threshold)
    residual = {str(i): b.has_residual for i, b in enumerate(net.blocks)}

    def update(params, masks):
        new = {}
        for k, m in masks.items():
            gamma = params["blocks"][k]["dw_bn"]["gamma"]
            alive = m * (jnp.abs(gamma) >= threshold).astype(jnp.float32)
            if not residual[k]:
                # a non-residual block is the only path through the chain:
                # if everything fell below threshold, revive the strongest
                # previously-alive atom (rematerialize.py does the same).
                best = jnp.argmax(jnp.abs(gamma) * m)
                revive = (jnp.arange(m.shape[0]) == best).astype(jnp.float32) * m
                alive = jnp.where(jnp.sum(alive) == 0, revive, alive)
            new[k] = alive
        return new

    return update


def make_prune_event(net: Network, cfg: PruneConfig, stop_step: int):
    """The COMPLETE per-cadence prune event as one jit-compatible function —
    reached-target check, adaptive-rho feedback, and the conditional mask
    update — of (params, masks, rho_mult, step) -> (masks, rho_mult).

    The whole event runs on the device: the CLI dispatches it at the mask
    cadence, and the (step % interval == 0) & (step <= stop) gate it carries
    makes a dispatch off the cadence or past the stop a no-op.

    The reached check uses the in-jit linear form of
    utils/profiling.masked_macs (exact: every atom's expand/dw/SE/project
    MACs scale per-channel): effective = total - sum_b cost_b . (1 - m_b).

    `step` is the index of the JUST-COMPLETED step (ts.step after the
    sub-step), matching the host loop's step_i numbering."""
    from ..utils.profiling import profile_network

    update = make_mask_update(net, cfg)
    prof = profile_network(net)
    total = float(prof.total_macs)
    costs = {str(i): jnp.asarray(c, jnp.float32) for i, c in prof.atom_costs.items()}
    interval = int(cfg.mask_interval)
    target = float(cfg.target_flops)
    adaptive = cfg.rho_schedule == "adaptive" and target > 0

    def event(params, masks, rho_mult, step):
        do = (step % interval == 0) & (step <= stop_step)
        if target > 0:
            eff = jnp.asarray(total, jnp.float32)
            for k, m in masks.items():
                eff = eff - jnp.sum(costs[k] * (1.0 - m))
            reached = eff <= target
        else:
            reached = jnp.asarray(False)
        if adaptive and rho_mult is not None:
            new_rho = jnp.clip(
                rho_mult * jnp.where(reached, 1.0 - cfg.rho_adapt_rate, 1.0 + cfg.rho_adapt_rate),
                cfg.rho_adapt_min, cfg.rho_adapt_max)
            rho_mult = jnp.where(do, new_rho, rho_mult)
        new_masks = update(params, masks)
        apply_update = do & ~reached
        masks = {k: jnp.where(apply_update, new_masks[k], m) for k, m in masks.items()}
        return masks, rho_mult

    return event


def mask_summary(net: Network, masks) -> dict:
    """Host-side logging payload: alive atom counts + effective MACs — the
    'remaining FLOPs' line the reference logs during shrinkage."""
    np_masks = {int(k): np.asarray(v) for k, v in masks.items()}
    alive = int(sum(m.sum() for m in np_masks.values()))
    total = int(sum(m.size for m in np_masks.values()))
    return {
        "alive_atoms": alive,
        "total_atoms": total,
        "effective_macs": masked_macs(net, np_masks),
    }
