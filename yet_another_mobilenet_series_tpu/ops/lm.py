"""Layers of the token-model family (models/lm.py): RMSNorm, rotary
embedding, causal multi-head latent attention and plain multi-head attention
over one tiled causal core, the SiLU-gated MLP and the expert layer of one
expert-parallel share.

Everything is plain `jax.numpy`/`lax` for XLA as it is, but attention on a
TPU (below). Weights are float32 and cast to the compute dtype where they are
used; norms, the router, the softmax and the loss are float32 whatever the
compute dtype. Each piece of work sits under its named scope (obs/scopes.py).

Two things keep a long sequence inside a chip's memory:

- :func:`causal_attention` never holds more than one tile of scores
  (`ATTN_BLOCK` query rows by as many key rows): every tile on or below the
  diagonal (the causal prefix: about half the products of the full square are
  never formed) is met under a running softmax, and a hand-written backward
  makes each tile again from the rows' log-sum-exp instead of keeping any
  probabilities. Where the step is lowered for a TPU and the shapes fit, the
  tiles live in VMEM inside two fused kernels (ops/lm_attention.py); one loop
  body for XLA meets them everywhere else, and is the kernels' oracle.
- :func:`expert_layer` sorts the (token, expert) assignments so that those of
  the experts HELD HERE come first, grouped by expert, and multiplies them
  with `lax.ragged_dot` (a grouped matmul that skips the rows outside its
  groups). It gathers, multiplies and sums back `capacity_rows` rows, twice
  what the share expects of the batch, a number the shapes give; ONE
  `lax.cond` a site takes the same body over EVERY assignment row on a step
  whose held assignments do not fit. So every assignment to a held expert is
  computed whatever the load: nothing is dropped. Assignments to experts that
  other shares hold are left out; nothing stands in for them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.scopes import scope
from . import lm_attention

Array = jax.Array


def rms_norm(x: Array, gain: Array, eps: float) -> Array:
    """x * rsqrt(mean(x^2) + eps) * gain over the last axis, in float32."""
    with scope("norm"):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
        return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rope_tables(seq_len: int, dim: int, theta: float) -> tuple[Array, Array]:
    """(cos, sin), each (seq_len, dim // 2), float32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate (..., S, heads, dim): channel i pairs with channel i + dim/2
    (the half-split convention of the Hugging Face implementations)."""
    with scope("rope"):
        x32 = x.astype(jnp.float32)
        a, b = jnp.split(x32, 2, axis=-1)
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1).astype(x.dtype)


# Query rows, and key rows, of one tile of scores: what causal_attention holds at once.
ATTN_BLOCK = 512
# The names (jax.ad_checkpoint.checkpoint_name) of what attention's backward keeps beside its operands: a
# jax.checkpoint around a layer that saves these two (models/lm.py) does not run the forward a second time.
ATTN_OUT_NAME = "attn_out"
ATTN_LSE_NAME = "attn_lse"


def _tile_scores(q, k, first_q, first_k, scale):
    """Masked float32 scores of query rows [first_q, ...) against key rows
    [first_k, ...): q (B, H, bq, D), k (B, H, bk, D) -> (B, H, bq, bk)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    rows = first_q + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = first_k + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where(cols <= rows, s, -jnp.inf)


def _rows(x, i, block):
    """Rows [i * block, (i + 1) * block) of the sequence axis of (B, H, S, ...)."""
    return lax.dynamic_slice_in_dim(x, i * block, block, axis=2)


def _add_rows(x, rows, i, block):
    return lax.dynamic_update_slice_in_dim(x, _rows(x, i, block) + rows.astype(x.dtype), i * block, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _blocked_attention(q, k, v, scale, block):
    return _blocked_attention_fwd(q, k, v, scale, block)[0]


def _by_lowering(kernels, loops, scale, block, *operands):
    """The fused kernels of ops/lm_attention.py where the shapes fit them
    (`lm_attention.fuses`) AND the step is lowered for a TPU, else the tile
    loops below. `lax.platform_dependent` decides at lowering, so a compile
    for a described chip from a CPU process takes the kernels and a CPU the
    loops; at shapes the kernels do not take the lowering is the loops' alone."""
    q, v = operands[0], operands[2]
    loops = functools.partial(loops, scale=scale, block=block)
    if not lm_attention.fuses(q.shape[2], block, q.shape[3], v.shape[3], q.dtype):
        return loops(*operands)
    return lax.platform_dependent(*operands, tpu=functools.partial(kernels, scale=scale, block=block), default=loops)


def _blocked_attention_fwd(q, k, v, scale, block):
    """Kept for the backward pass: the output and each row's log-sum-exp,
    never a tile. Both carry a name, whichever lowering made them: under a
    `jax.checkpoint` that saves `ATTN_OUT_NAME` and `ATTN_LSE_NAME` they are
    what the layer holds across the step, and the backward's second run of the
    layer makes `q`, `k`, `v` again (cheap projections) and not this forward."""
    from jax.ad_checkpoint import checkpoint_name  # not an attribute of `jax`; an alias module of what `import jax` loaded

    with scope("attn_core"):
        out, lse = _by_lowering(lm_attention.attention_fwd, loops_fwd, scale, block, q, k, v)
        out, lse = checkpoint_name(out, ATTN_OUT_NAME), checkpoint_name(lse, ATTN_LSE_NAME)
        return out, (q, k, v, out, lse)


def loops_fwd(q, k, v, scale, block):
    """ONE loop body for every tile: query block i meets key blocks 0..i (the
    causal prefix; the tiles above the diagonal are never formed) under a
    running row maximum and row sum."""
    b, h, seq, _ = q.shape

    def query_block(i, carry):
        out, lse = carry
        qi = _rows(q, i, block)

        def key_block(j, state):
            top, total, acc = state
            s = _tile_scores(qi, _rows(k, j, block), i * block, j * block, scale)
            # the row maximum behind a barrier: left to itself XLA:TPU turns "reduce,
            # broadcast back, subtract" over a row into a reduce-window as wide as the
            # row (work quadratic in the row; PERF.md, PR 27)
            new_top = jnp.maximum(top, lax.optimization_barrier(jnp.max(s, axis=-1)))
            weights = jnp.exp(s - new_top[..., None])
            keep = jnp.exp(top - new_top)
            acc = acc * keep[..., None] + jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype),
                                                     _rows(v, j, block), preferred_element_type=jnp.float32)
            return new_top, total * keep + jnp.sum(weights, axis=-1), acc

        zeros = jnp.zeros((b, h, block), jnp.float32)
        top, total, acc = lax.fori_loop(0, i + 1, key_block, (
            zeros - jnp.inf, zeros, jnp.zeros((b, h, block, v.shape[-1]), jnp.float32)))
        out = lax.dynamic_update_slice_in_dim(out, (acc / total[..., None]).astype(out.dtype), i * block, axis=2)
        return out, lax.dynamic_update_slice_in_dim(lse, top + jnp.log(total), i * block, axis=2)

    return lax.fori_loop(0, seq // block, query_block, (
        jnp.zeros((b, h, seq, v.shape[-1]), v.dtype), jnp.zeros((b, h, seq), jnp.float32)))


def _blocked_attention_bwd(scale, block, kept, g):
    with scope("attn_core"):
        return _by_lowering(lm_attention.attention_bwd, loops_bwd, scale, block, *kept, g)


def loops_bwd(q, k, v, out, lse, g, scale, block):
    """Each key block once: its dK and dV gather over the query blocks i >= j
    that see it, each tile's probabilities made again from the row's
    log-sum-exp; dQ is added into its rows as the tiles go by."""
    seq = q.shape[2]
    # sum_k P dP of every row, which the softmax's backward subtracts: it is g . out
    inner = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def key_block(j, carry):
        dq, dk, dv = carry
        kj, vj = _rows(k, j, block), _rows(v, j, block)

        def query_block(i, state):
            dq, dkj, dvj = state
            qi, gi = _rows(q, i, block), _rows(g, i, block)
            s = _tile_scores(qi, kj, i * block, j * block, scale)
            probs = jnp.exp(s - _rows(lse, i, block)[..., None])
            dvj = dvj + jnp.einsum("bhqk,bhqd->bhkd", probs.astype(g.dtype), gi, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gi, vj, preferred_element_type=jnp.float32)
            ds = (probs * (dp - _rows(inner, i, block)[..., None]) * scale).astype(q.dtype)
            dq = _add_rows(dq, jnp.einsum("bhqk,bhkd->bhqd", ds, kj, preferred_element_type=jnp.float32), i, block)
            dkj = dkj + jnp.einsum("bhqk,bhqd->bhkd", ds, qi, preferred_element_type=jnp.float32)
            return dq, dkj, dvj

        dq, dkj, dvj = lax.fori_loop(j, seq // block, query_block, (
            dq, jnp.zeros(kj.shape, jnp.float32), jnp.zeros(vj.shape, jnp.float32)))
        return (dq, lax.dynamic_update_slice_in_dim(dk, dkj.astype(dk.dtype), j * block, axis=2),
                lax.dynamic_update_slice_in_dim(dv, dvj.astype(dv.dtype), j * block, axis=2))

    dq, dk, dv = lax.fori_loop(0, seq // block, key_block, (
        jnp.zeros(q.shape, jnp.float32), jnp.zeros_like(k), jnp.zeros_like(v)))
    return dq.astype(q.dtype), dk, dv


_blocked_attention.defvjp(_blocked_attention_fwd, _blocked_attention_bwd)


def causal_attention(q: Array, k: Array, v: Array, *, scale: float, block: int | None = None) -> Array:
    """Causal softmax(q k^T * scale) v, float32 softmax, a tile of `block`
    query rows by `block` key rows at a time and never one kept, forward and
    backward (the backward recomputes each tile): in the fused TPU kernels of
    ops/lm_attention.py where the shapes fit them (q, k and v filled with
    zero channels to a multiple of 128 where that is all they lack:
    `lm_attention.fitting_dims`) and the step is lowered for a TPU, else in
    the loops over tiles. q, k (B, S, H, D); v (B, S, H, Dv) -> (B, S, H,
    Dv)."""
    seq = q.shape[1]
    block = min(block or ATTN_BLOCK, seq)
    if seq % block:
        raise ValueError(f"sequence length {seq} is not a multiple of the attention block {block}")
    with scope("attn_core"):
        v_dim = v.shape[-1]
        wide, v_wide = lm_attention.fitting_dims(seq, block, q.shape[-1], v_dim, q.dtype)
        fill = lambda x, width: jnp.pad(x, [(0, 0)] * 3 + [(0, width - x.shape[-1])])  # noqa: E731
        if wide != q.shape[-1]:  # zero channels, so that the kernels take the call (192 -> 256): exact
            q, k = fill(q, wide), fill(k, wide)
        if v_wide != v_dim:  # and v's (64 -> 128): the filled channels of the output are zeros, and are cut off
            v = fill(v, v_wide)
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))  # heads lead: batch and head are the tiles' batch axes
        out = jnp.swapaxes(_blocked_attention(q, k, v, scale, block), 1, 2)
        return out if v_wide == v_dim else out[..., :v_dim]


def mla_attention(p: dict, x: Array, cos: Array | None, sin: Array | None, *, heads: int, nope: int, rope: int,
                  v_dim: int, kv_rank: int, eps: float) -> Array:
    """Multi-head latent attention (DeepSeek-V2's MLA, as glm4_moe_lite and
    kimi_linear have it), training form: the latents are expanded to per-head
    keys and values. `k_rope` is ONE head, shared by all `heads`. Two options,
    read from what is handed in: q through a low-rank pair with its norm
    (`p` holds `q_a`, `q_norm`, `q_b`) or one projection (`p` holds `q`); and
    with `cos` None nothing is rotated: the `rope` channels stay as they are
    projected (`mla_use_nope`). x (B, S, h) -> (B, S, h)."""
    cd = x.dtype
    b, s, _ = x.shape
    low_rank_q = "q_a" in p
    with scope("attn_proj"):
        c_q = x @ p["q_a"].astype(cd) if low_rank_q else None
        kv_a = x @ p["kv_a"].astype(cd)
    if low_rank_q:
        c_q = rms_norm(c_q, p["q_norm"], eps)
    c_kv = rms_norm(kv_a[..., :kv_rank], p["kv_norm"], eps)
    with scope("attn_proj"):
        q = ((c_q @ p["q_b"].astype(cd)) if low_rank_q else (x @ p["q"].astype(cd))).reshape(b, s, heads, nope + rope)
        kv = (c_kv @ p["kv_b"].astype(cd)).reshape(b, s, heads, nope + v_dim)
    if cos is not None:
        q_rope = apply_rope(q[..., nope:], cos, sin)
        k_rope = apply_rope(kv_a[..., None, kv_rank:], cos, sin)
    else:
        k_rope = kv_a[..., None, kv_rank:]
    with scope("attn_core"):
        if cos is not None:
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1)
    out = causal_attention(q, k, kv[..., nope:], scale=(nope + rope) ** -0.5)
    with scope("attn_proj"):
        return out.reshape(b, s, heads * v_dim) @ p["o"].astype(cd)


def mha_attention(p: dict, x: Array, cos: Array | None, sin: Array | None, *, heads: int, head_dim: int,
                  kv_heads: int | None = None, scale: float | None = None) -> Array:
    """Plain multi-head attention (`ouro`) and grouped-query attention
    (`granitemoehybrid`'s attention layers): q, k, v three projections, no
    bias, no q/k norm; `heads` query heads and `kv_heads` (None: as many) key
    and value heads of `head_dim` channels, query head i reading key/value
    head i // (heads / kv_heads); ALL channels of q and k rotated, or none
    where `cos` is None; scores times `scale` (None: head_dim^-0.5). The
    causal core is :func:`causal_attention`, as for the latent archs, with the
    key/value heads repeated before it (autodiff sums the copies' gradients
    back). x (B, S, h) -> (B, S, h)."""
    cd = x.dtype
    b, s, _ = x.shape
    kv_heads = kv_heads or heads
    with scope("attn_proj"):
        q, k, v = ((x @ p[name].astype(cd)).reshape(b, s, n, head_dim)
                   for name, n in (("q", heads), ("k", kv_heads), ("v", kv_heads)))
    if cos is not None:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if kv_heads != heads:
        with scope("attn_core"):
            k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    out = causal_attention(q, k, v, scale=head_dim ** -0.5 if scale is None else scale)
    with scope("attn_proj"):
        return out.reshape(b, s, heads * head_dim) @ p["o"].astype(cd)


def gated_mlp(p: dict, x: Array) -> Array:
    """down(silu(gate x) * up x): the dense MLP and the shared expert."""
    cd = x.dtype
    with scope("mlp"):
        return (jax.nn.silu(x @ p["gate"].astype(cd)) * (x @ p["up"].astype(cd))) @ p["down"].astype(cd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of(x: Array, order: Array, at: Array, repeat: int) -> Array:
    """`jnp.repeat(x, repeat, axis=0)[order]` without holding the repeated
    array, for `order` a permutation of the repeated rows or its first rows,
    and `at[i]` where repeated row i went (`len(order)` for one that is not
    among them). The cotangent is :func:`_sum_of_rows`, a gather, where
    autodiff would scatter-add."""
    return x[order // repeat]


def _rows_of_fwd(x, order, at, repeat):
    return x[order // repeat], (order, at)


def _rows_of_bwd(repeat, kept, g):
    return _sum_of_rows(g, *kept, repeat), None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_of_rows(x: Array, order: Array, at: Array, repeat: int) -> Array:
    """The transpose of :func:`_rows_of`: out[t] = the sum of the rows
    `x[at[i]]` over the `repeat` copies i of t, a copy that has no row (`at[i]`
    = `len(x)`) counting zero; summed in float32 and rounded once. The
    cotangent is `_rows_of`."""
    rows = x.at[at].get(mode="fill", fill_value=0).reshape(-1, repeat, x.shape[-1])
    return jnp.sum(rows.astype(jnp.float32), axis=1).astype(x.dtype)


def _sum_of_rows_fwd(x, order, at, repeat):
    return _sum_of_rows(x, order, at, repeat), (order, at)


def _sum_of_rows_bwd(repeat, kept, g):
    return _rows_of(g, *kept, repeat), None, None


_sum_of_rows.defvjp(_sum_of_rows_fwd, _sum_of_rows_bwd)


@jax.custom_vjp
def _unsort(x: Array, order: Array, inverse: Array) -> Array:
    """`x[inverse]`: sorted rows back in assignment order; the cotangent is `g[order]`."""
    return x[inverse]


def _unsort_fwd(x, order, inverse):
    return x[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def route(router_w: Array, bias: Array, x: Array, *, top_k: int, scaling: float):
    """`noaux_tc` routing without groups. x (T, h) -> (expert ids (T, k),
    weights (T, k) float32, assignments per expert (E,) float32).

    scores = sigmoid(W_r x) in float32; the SELECTION is the top-k of
    scores + bias, the WEIGHTS are the selected scores themselves (no bias),
    divided by their sum, times `scaling`. `bias` is state, not a parameter:
    it gets no gradient."""
    with scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, ids = lax.top_k(scores + lax.stop_gradient(bias)[None, :], top_k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scaling
        load = jnp.sum(jax.nn.one_hot(ids, scores.shape[-1], dtype=jnp.float32), axis=(0, 1))
        return ids, weights, load


# Rows the capacity is a multiple of. Coarser than a sublane tile on purpose: a bounded branch
# shorter than this saves nothing worth a second body, and at a dozen tokens (where
# tests/benchmark_tests count every `ragged_dot` the forward traces) a site stays ONE body.
CAPACITY_TILE = 64


def capacity_rows(assignments: int, held: int, n_experts: int) -> int:
    """The rows an expert layer's bounded branch works on: twice what a share
    holding `held` of `n_experts` experts expects of `assignments` (tokens x
    top_k), to a whole `CAPACITY_TILE`. Read from the shapes; nothing sets it."""
    return -(-2 * assignments * held // (CAPACITY_TILE * n_experts)) * CAPACITY_TILE


def _grouped_mlp(experts: dict, rows: Array, group_sizes: Array) -> Array:
    """down(silu(gate row) * up row), each row by its group's expert; the rows
    past the groups are not written."""
    cd = rows.dtype
    with scope("moe_experts"):
        hidden = (jax.nn.silu(lax.ragged_dot(rows, experts["gate"].astype(cd), group_sizes))
                  * lax.ragged_dot(rows, experts["up"].astype(cd), group_sizes))
        return lax.ragged_dot(hidden, experts["down"].astype(cd), group_sizes)


def _not_written(out: Array, count: Array) -> Array:
    """How many of the `count` held assignments were not computed, counted from
    what the grouped matmul WROTE, not from the ids: an assignment to a held
    expert whose row came back all zero. `out` is zero wherever no held
    assignment's row is."""
    written = jnp.any(lax.stop_gradient(out) != 0, axis=-1)
    return count.astype(jnp.float32) - jnp.sum(written.astype(jnp.float32))


def _every_row(top_k: int, xf, weights, experts, order, inverse, group_sizes):
    """The held assignments through their experts and back into token rows, at
    full length: every assignment's row is gathered, goes through the grouped
    matmuls (which skip the rows past the groups) and is un-sorted, whatever
    the share holds. -> (y (tokens, h), the held assignments not computed)."""
    cd = xf.dtype
    tokens, h = xf.shape
    count = jnp.sum(group_sizes)
    with scope("moe_dispatch"):
        in_group = (jnp.arange(order.shape[0]) < count)[:, None]
        # rows outside the groups are never written by the grouped matmul, in
        # either pass: a select keeps what they hold out of both
        rows = jnp.where(in_group, _rows_of(xf, order, inverse, top_k), jnp.zeros((), cd))
    out = _grouped_mlp(experts, rows, group_sizes)
    with scope("moe_combine"):
        out = jnp.where(in_group, out, jnp.zeros((), cd))
        out = _unsort(out, order, inverse).reshape(tokens, top_k, h)
        return jnp.sum(out * weights[..., None].astype(cd), axis=1), _not_written(out, count)


def _held_rows(capacity: int, top_k: int, xf, weights, experts, order, inverse, group_sizes):
    """The same, working on the first `capacity` rows of the sorted order,
    which must hold every held assignment: those rows alone are gathered,
    multiplied, weighted (in float32) and summed back into their tokens' rows
    (in float32, rounded once)."""
    cd = xf.dtype
    count = jnp.sum(group_sizes)
    with scope("moe_dispatch"):
        first = order[:capacity]
        live = (jnp.arange(capacity) < count)[:, None]
        # where an assignment's row is; `capacity`, which is no row, for one that another share holds
        at = jnp.where(inverse < count, inverse, capacity)
        rows = jnp.where(live, _rows_of(xf, first, at, top_k), jnp.zeros((), cd))
    out = _grouped_mlp(experts, rows, group_sizes)
    with scope("moe_combine"):
        out = jnp.where(live, out, jnp.zeros((), cd))
        weight = _rows_of(weights.reshape(-1, 1), first, at, 1)
        y = _sum_of_rows((out.astype(jnp.float32) * weight).astype(cd), first, at, top_k)
        return y, _not_written(out, count)


def _branches(top_k: int, capacity: int, assignments: int) -> list:
    """A site's bodies, indexed by the predicate `held rows <= capacity`:
    `_every_row`, and `_held_rows` where it is the shorter. A share holding
    half the experts or more (the uncut layer) has the first alone."""
    every_row = functools.partial(_every_row, top_k)
    return [every_row, functools.partial(_held_rows, capacity, top_k)] if capacity < assignments else [every_row]


def _one_of(branches: list, fits: Array, *operands):
    """`branches[fits]` of `operands`: ONE `lax.cond`, which runs one branch."""
    return lax.cond(fits, *branches[::-1], *operands) if len(branches) > 1 else branches[0](*operands)


# Both halves are jitted so that a model's sites, which share their shapes, share ONE trace of the
# two branches (and of their derivatives): traced a site at a time they cost a token cell ~2.5 s of set-up.
@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _held_experts_forward(top_k: int, capacity: int, xf, weights, experts, order, inverse, group_sizes):
    branches = _branches(top_k, capacity, order.shape[0])
    fits = jnp.sum(group_sizes) <= capacity
    y, dropped = _one_of(branches, fits, xf, weights, experts, order, inverse, group_sizes)
    return y, dropped, (fits & (len(branches) > 1)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1), inline=True)
def _held_experts_backward(top_k: int, capacity: int, xf, weights, experts, order, inverse, group_sizes, g_y):
    def pulled_back(branch):
        def fn(xf, weights, experts, order, inverse, group_sizes, g_y):
            _, pull = jax.vjp(lambda *d: branch(*d, order, inverse, group_sizes)[0], xf, weights, experts)
            return pull(g_y)
        return fn

    branches = [pulled_back(b) for b in _branches(top_k, capacity, order.shape[0])]
    return _one_of(branches, jnp.sum(group_sizes) <= capacity, xf, weights, experts, order, inverse, group_sizes, g_y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(top_k: int, capacity: int, xf, weights, experts, order, inverse, group_sizes):
    """:func:`_held_rows` where this step's held assignments fit in `capacity`
    rows, else :func:`_every_row`, which drops nothing whatever the load: one
    `lax.cond` forward and one backward. -> (y, the held assignments not
    computed, 1.0 where the bounded branch ran). The backward differentiates
    the branch it takes INSIDE its own `cond`: differentiating through a
    `cond` hands every branch's residuals across, the untaken one's as zeros
    at full length."""
    return _held_experts_forward(top_k, capacity, xf, weights, experts, order, inverse, group_sizes)


def _held_experts_fwd(top_k, capacity, *operands):
    return _held_experts_forward(top_k, capacity, *operands), operands


def _held_experts_bwd(top_k, capacity, operands, g):
    g_xf, g_weights, g_experts = _held_experts_backward(top_k, capacity, *operands, g[0])
    # ONE of the `cond`'s outputs behind a barrier. Left free, XLA:TPU's conditional code motion sinks what reads the
    # experts' gradients (the square sums of the clip and of the reported norms) into both branches of every site, and
    # kimi_linear's executable quadruples (0.11 -> 0.40 GiB of code: +2.4 s to read it from the compile cache at every
    # warm start). One output is enough to stop that; all three would cost GLM's step 0.65 GiB of temporaries, this one
    # costs it 16 MiB (compiles for the described chip and chip runs, PERF.md, PR 34).
    g_experts = {**g_experts, "down": lax.optimization_barrier(g_experts["down"])}
    return g_xf, g_weights, g_experts, None, None, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def expert_layer(p: dict, bias: Array, x: Array, *, top_k: int, scaling: float, held: int,
                 share_index: int):
    """The routed experts of ONE expert-parallel share: routes every token
    over ALL experts (`p["router"]` keeps its published width) and returns the
    sum, over the selected experts that this share holds (ids
    ``[share_index * held, (share_index + 1) * held)``; `p["experts"]` holds
    exactly those), of weight * expert(token). The shared expert is not in
    here. x (B, S, h) -> (y (B, S, h), load over all experts (E,), counters,
    the selected expert ids (B * S, top_k)).
    """
    b, s, h = x.shape
    tokens = b * s
    xf = x.reshape(tokens, h)
    ids, weights, load = route(p["router"], bias, xf, top_k=top_k, scaling=scaling)
    first = share_index * held
    with scope("moe_dispatch"):
        flat = ids.reshape(-1)
        here = (flat >= first) & (flat < first + held)
        # held experts first, grouped by expert; the rest, which no group covers, last
        order = jnp.argsort(jnp.where(here, flat - first, held), stable=True)
        inverse = jnp.argsort(order)
        group_sizes = load[first:first + held].astype(jnp.int32)
    y, dropped, bounded = _held_experts(top_k, capacity_rows(tokens * top_k, held, load.shape[0]),
                                        xf, weights, p["experts"], order, inverse, group_sizes)
    with scope("moe_combine"):
        held_load = group_sizes.astype(jnp.float32)
        counters = {
            "assignments_here": jnp.sum(here.astype(jnp.float32)),
            "dropped": dropped,
            "bounded": bounded,
            "load_max_over_mean": jnp.max(held_load) / jnp.maximum(jnp.mean(held_load), 1.0),
        }
    return y.reshape(b, s, h), load, counters, ids
