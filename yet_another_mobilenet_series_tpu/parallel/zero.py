"""Cross-replica sharding of the weight update (PAPERS.md:5,
arXiv:2004.13336) — the ZeRO-style option on top of data parallelism.

Instead of every replica redundantly applying the identical optimizer update
(replicated RMSProp/momentum accumulators, 2x param memory each), the update
is split across the 'data' axis:

  grads --psum_scatter--> 1/N shard per device          (half the allreduce)
  each device updates its shard (accumulators live sharded: memory/N)
  new params --all_gather--> replicated again           (the other half)

Total communication matches plain DP's allreduce (reduce-scatter+all-gather
== allreduce), but update FLOPs and optimizer memory drop by N. For the
MobileNet-scale models here the win is small; the component exists because
it is the one beyond-DP parallelism with grounding in the reference workload
(SURVEY.md §2 parallelism inventory) and it matters at the 256-chip
acceptance point's batch sizes.

Used inside the shard_map'd train step: ``make_zero_update`` returns the
per-device update; ``init_opt_state``/``opt_state_specs`` build the globally
sharded accumulator tree ((n*chunk,) flat leaves, PartitionSpec('data')).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.scopes import scope
from .mesh import DATA_AXIS


def _chunk(total: int, n: int) -> int:
    return -(-total // n)


def _pad_flat(x, n: int):
    """(total,) -> (n*chunk,) zero-padded flat view."""
    total = x.size
    chunk = _chunk(total, n)
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, n * chunk - total))


def _shard_of(x, idx, n: int):
    """This device's (chunk,) slice of a (replicated) leaf."""
    chunk = _chunk(x.size, n)
    return lax.dynamic_slice(_pad_flat(x, n), (idx * chunk,), (chunk,))


def shard_params_local(params, idx, n: int):
    return jax.tree.map(lambda p: _shard_of(p, idx, n), params)


def make_zero_update(optimizer: optax.GradientTransformation, n: int, axis_name: str = DATA_AXIS):
    """Returns update(grads_local, opt_state_shard, params) ->
    (new_params_replicated, new_opt_state_shard, global_grad_norm).
    Call inside shard_map; ``grads_local`` are this device's UN-averaged
    local gradients (no pmean — the mean happens in the psum_scatter)."""

    def update(grads, opt_state_sh, params):
        idx = lax.axis_index(axis_name)

        def scatter(g):
            chunk = _chunk(g.size, n)
            g2 = _pad_flat(g, n).reshape(n, chunk)
            return lax.psum_scatter(g2, axis_name, scatter_dimension=0, tiled=False) / n

        # obs/scopes.py: the two halves of the allreduce are `grad_sync`, the
        # update of this device's shard `optim`
        with scope("grad_sync"):
            g_sh = jax.tree.map(scatter, grads)
        with scope("optim"):
            p_sh = shard_params_local(params, idx, n)
            updates, new_opt_sh = optimizer.update(g_sh, opt_state_sh, p_sh)
            new_p_sh = optax.apply_updates(p_sh, updates)

        def gather(ns, orig):
            full = lax.all_gather(ns, axis_name, tiled=True)  # (n*chunk,)
            return full[: orig.size].reshape(orig.shape).astype(orig.dtype)

        with scope("grad_sync"):
            new_params = jax.tree.map(gather, new_p_sh, params)
        with scope("optim"):
            gnorm = jnp.sqrt(lax.psum(optax.global_norm(g_sh) ** 2, axis_name))
        return new_params, new_opt_sh, gnorm

    return update


def _local_init(optimizer, params, idx, n):
    return optimizer.init(shard_params_local(params, idx, n))


def opt_state_specs(optimizer: optax.GradientTransformation, params, n: int):
    """PartitionSpec tree for the globally-sharded optimizer state: flat
    accumulator leaves are P('data'); scalar bookkeeping (e.g. schedule
    counts) is replicated."""
    abstract = jax.eval_shape(lambda p: _local_init(optimizer, p, 0, n), params)
    return jax.tree.map(lambda l: P(DATA_AXIS) if l.ndim >= 1 else P(), abstract)


def init_opt_state(optimizer: optax.GradientTransformation, params, mesh: Mesh):
    """Builds the sharded optimizer state as global arrays over the mesh:
    each accumulator leaf is (n*chunk,) flat, device d holding shard d."""
    n = mesh.size
    specs = opt_state_specs(optimizer, params, n)
    fn = jax.shard_map(
        lambda p: _local_init(optimizer, p, lax.axis_index(DATA_AXIS), n),
        mesh=mesh,
        in_specs=(P(),),
        out_specs=specs,
        # check_vma=False everywhere in parallel/: see the contract note at
        # dp.py make_dp_train_step (the conv + BN pair's local-partial grads) — pinned
        # by tests/test_parallel.py::test_check_vma_contract
        check_vma=False,
    )
    return jax.jit(fn)(params)


def place_opt_state(opt_state_flat, mesh: Mesh):
    """Places a flat-sharded opt-state tree onto the mesh: (n*chunk,) leaves
    split on 'data', scalars replicated."""
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(DATA_AXIS) if getattr(x, "ndim", 0) >= 1 else P())
        ),
        opt_state_flat,
    )


# ---------------------------------------------------------------------------
# Gathered (params-shaped) <-> flat-sharded conversions.
#
# The CANONICAL external form of the optimizer state is params-shaped and
# replicated: checkpoints store it that way (chip-count portable — a run
# saved on 8 chips resumes on 256; multi-host saves need no cross-host
# device_get) and NAS rematerialization slices it with the same channel
# slicers as the params (nas/rematerialize.py). The flat (n*chunk,) sharded
# form exists only inside a live mesh.
# ---------------------------------------------------------------------------


def gather_opt_state(opt_state_flat, params):
    """Flat-sharded -> params-shaped replicated (jit-able on the mesh)."""
    from ..utils.treeutil import map_params_shaped

    pstruct = jax.tree.structure(params)

    def unflat(sub):
        return jax.tree.map(lambda f, p: f[: p.size].reshape(p.shape), sub, params)

    return map_params_shaped(opt_state_flat, pstruct, unflat)


def scatter_opt_state(opt_state_gathered, params, mesh: Mesh):
    """Params-shaped -> flat leaves sharded over THIS mesh (any size)."""
    from ..utils.treeutil import map_params_shaped

    n = mesh.size
    pstruct = jax.tree.structure(params)

    def flat(sub):
        return jax.tree.map(lambda x: _pad_flat(jnp.asarray(x), n), sub)

    return place_opt_state(map_params_shaped(opt_state_gathered, pstruct, flat), mesh)
