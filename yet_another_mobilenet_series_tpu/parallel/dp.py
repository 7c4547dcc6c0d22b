"""Data-parallel train/eval steps over the mesh (the apex-DDP replacement,
SURVEY.md §2 #12 and §3.1).

One ``jit(shard_map(step))`` per step: batch sharded on 'data', every state
pytree replicated. Gradients are pmean'd and BN moments psum'd *inside* the
program, so XLA overlaps the collectives with backprop the way apex's bucketed
allreduce overlapped with autograd — except scheduled by the compiler, not by
hand. Optionally the optimizer update itself is sharded across replicas and
the fresh params all-gathered (PAPERS.md:5, arXiv:2004.13336 — ZeRO-style
cross-replica weight-update sharding) to cut update time and optimizer memory.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import Config
from ..models.specs import Network
from ..train.steps import TrainState, make_eval_step, make_train_step
from .mesh import DATA_AXIS


def make_dp_train_step(
    net: Network,
    cfg: Config,
    optimizer,
    lr_fn: Callable,
    mesh: Mesh,
    *,
    penalty_fn=None,
    params_example=None,
    clip_shard_aware: bool = False,
):
    """jitted (ts, batch, rng) -> (ts, metrics) over the mesh.

    ts is fully replicated; batch is sharded on the 'data' axis. The per-shard
    rng is folded with the device's axis index so dropout/augment noise is
    decorrelated across replicas. With cfg.dist.shard_optimizer the optimizer
    accumulators are sharded on 'data' and the update runs ZeRO-style
    (parallel/zero.py).
    """
    shard_opt = cfg.dist.shard_optimizer
    sharded_update = None
    opt_spec = P()
    if shard_opt:
        if cfg.optim.grad_clip_norm > 0 and not clip_shard_aware:
            # a plain optax clip inside the ZeRO update would clip each
            # gradient SHARD by its own local norm (~global/sqrt(N)); the
            # caller must build the optimizer with
            # make_optimizer(..., shard_axis=DATA_AXIS) and attest it here
            raise ValueError(
                "grad_clip_norm with shard_optimizer requires an optimizer built with "
                "make_optimizer(..., shard_axis=DATA_AXIS); pass clip_shard_aware=True to attest"
            )
        from . import zero

        sharded_update = zero.make_zero_update(optimizer, mesh.size)
        if params_example is None:
            params_example, _ = jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0)))
        opt_spec = zero.opt_state_specs(optimizer, params_example, mesh.size)
    inner = make_train_step(
        net, cfg, optimizer, lr_fn, axis_name=DATA_AXIS, penalty_fn=penalty_fn, sharded_update=sharded_update,
        platform=mesh.devices.flat[0].platform,
    )
    if cfg.train.guard.enable:
        # device-side non-finite skip-and-rollback (train/guard.py). MUST
        # wrap inside the jit/donation boundary: the select reads the
        # pre-step buffers the compiled program donates.
        from ..train.guard import wrap_step_fn

        inner = wrap_step_fn(inner)

    def shard_fn(ts: TrainState, batch, rng):
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        return inner(ts, batch, rng)

    ts_spec = TrainState(
        step=P(), params=P(), state=P(), opt_state=opt_spec, ema_params=P(), ema_state=P(), masks=P(), rho_mult=P()
    )
    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(ts_spec, P(DATA_AXIS), P()),
        out_specs=(ts_spec, P()),
        # check_vma=False is LOAD-BEARING for the conv + BatchNorm pair: its
        # closed-form backward returns LOCAL partial dgamma/dbeta/dW that the
        # step's pmean/psum_scatter combines (ops/layers.py _bn_grad_sums
        # contract). Flipping to check_vma=True changes shard_map's
        # replication semantics — revisit that VJP first
        # (pinned by tests/test_parallel.py::test_check_vma_contract).
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0,))


def make_dp_eval_step(net: Network, cfg: Config, mesh: Mesh):
    """jitted (params, state, batch, masks) -> summed metric counts."""
    inner = make_eval_step(net, cfg, axis_name=DATA_AXIS)
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def make_replica_sync_check(mesh: Mesh):
    """Returns check(tree) -> max over leaves of max |leaf_i - leaf_0| across
    replicas (exactly 0.0 iff every replica is bit-identical).

    The distributed 'race detector' of SURVEY.md §5: replicated state must be
    bit-identical on every device; drift means non-deterministic compute or a
    broken collective. Per-leaf element-wise comparison — a summed scalar
    checksum in f32 rounds away small single-leaf divergence over millions of
    parameters. Run every cfg.train.param_checksum_every steps (debug knob;
    the all_gather per leaf is transient but not free).
    """

    def shard_fn(tree):
        worst = jnp.zeros((), jnp.float32)
        for l in jax.tree.leaves(tree):
            all_l = lax.all_gather(l.astype(jnp.float32), DATA_AXIS)
            worst = jnp.maximum(worst, jnp.max(jnp.abs(all_l - all_l[0])))
        return worst

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
    return jax.jit(fn)
