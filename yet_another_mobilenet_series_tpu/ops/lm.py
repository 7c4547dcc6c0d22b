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
  what the share expects of the batch, a number the shapes give (four times
  under a stateless softmax router: `site_capacity`); ONE
  `lax.cond` a site takes the same body over EVERY assignment row on a step
  whose held assignments do not fit. So every assignment to a held expert is
  computed whatever the load: nothing is dropped. Assignments to experts that
  other shares hold are left out; nothing stands in for them.
"""

from __future__ import annotations

import functools
import math

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


def yarn_inv_freq(dim: int, spec) -> Array:
    """YaRN's frequencies (arXiv:2309.00071), as transformers'
    `_compute_yarn_parameters` makes them: the rotary frequencies of `dim`
    channels at `spec.rope_theta`, each blended between itself (extrapolated:
    the fast ones, which turn more than `beta_fast` times over the original
    context) and itself / `factor` (interpolated: those that turn fewer than
    `beta_slow` times) by a linear ramp between the two channel indices,
    truncated to whole channels. float32, (dim // 2,)."""
    base, original = spec.rope_theta, spec.original_max_position_embeddings

    def channel(rotations):  # the channel index whose frequency turns `rotations` times over the original context
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(channel(spec.beta_fast)), 0), min(math.ceil(channel(spec.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / ((high - low) or 0.001), 0.0, 1.0)
    freq = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return (1.0 / (spec.factor * freq)) * ramp + (1.0 / freq) * (1.0 - ramp)


def rope_tables_of(seq_len: int, head_dim: int, spec) -> tuple[Array, Array]:
    """(cos, sin) of a layer type's rotary embedding (config.RopeSpec), each
    (seq_len, rotated // 2) float32, where the first `rotated` =
    `partial_rotary_factor` x head_dim channels of a head turn (apply_rope
    passes the rest through): `default` at `rope_theta`, or `yarn` with both
    tables times `attention_factor`."""
    dim = int(head_dim * spec.partial_rotary_factor)
    if spec.rope_type == "default":
        return rope_tables(seq_len, dim, spec.rope_theta)
    if spec.rope_type != "yarn":
        raise ValueError(f"rope_type {spec.rope_type!r} is not one of default, yarn")
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * yarn_inv_freq(dim, spec)[None, :]
    return jnp.cos(angles) * spec.attention_factor, jnp.sin(angles) * spec.attention_factor


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate (..., S, heads, dim): channel i pairs with channel i + r/2
    (the half-split convention of the Hugging Face implementations) for the
    first r = 2 x cos.shape[-1] channels, which is all of them unless the
    tables are a partial rotary embedding's; the rest pass through."""
    with scope("rope"):
        rotated = 2 * cos.shape[-1]
        if rotated < x.shape[-1]:
            return jnp.concatenate([apply_rope(x[..., :rotated], cos, sin), x[..., rotated:]], axis=-1)
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


def _tile_scores(q, k, first_q, first_k, scale, window=None):
    """Masked float32 scores of query rows [first_q, ...) against key rows
    [first_k, ...): q (B, H, bq, D), k (B, H, bk, D) -> (B, H, bq, bk). A
    `window`: key k visible to query q where q - window < k <= q."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    rows = first_q + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = first_k + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    if window is None:
        return jnp.where(cols <= rows, s, -jnp.inf)
    return jnp.where((cols <= rows) & (cols > rows - window), s, -jnp.inf)


def window_reach(window: int, block: int) -> int:
    """How many key blocks before a query block's own a `window` reaches:
    query block i meets key blocks max(i - reach, 0)..i, and key block j
    query blocks j..j + reach (ops/lm_attention_kernels.py takes the same
    bounds). ceil((window - 1) / block): 1 where window = block."""
    return -(-(window - 1) // block)


def _rows(x, i, block):
    """Rows [i * block, (i + 1) * block) of the sequence axis of (B, H, S, ...)."""
    return lax.dynamic_slice_in_dim(x, i * block, block, axis=2)


def _add_rows(x, rows, i, block):
    return lax.dynamic_update_slice_in_dim(x, _rows(x, i, block) + rows.astype(x.dtype), i * block, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked_attention(q, k, v, scale, block, window):
    return _blocked_attention_fwd(q, k, v, scale, block, window)[0]


def _by_lowering(kernels, loops, scale, block, window, *operands):
    """The fused kernels of ops/lm_attention.py where the shapes fit them
    (`lm_attention.fuses`) AND the step is lowered for a TPU, else the tile
    loops below; both within the sliding `window` where one is given.
    `lax.platform_dependent` decides at lowering, so a compile for a described
    chip from a CPU process takes the kernels and a CPU the loops; at shapes
    the kernels do not take the lowering is the loops' alone."""
    q, v = operands[0], operands[2]
    loops = functools.partial(loops, scale=scale, block=block, window=window)
    if not lm_attention.fuses(q.shape[2], block, q.shape[3], v.shape[3], q.dtype):
        return loops(*operands)
    return lax.platform_dependent(*operands, tpu=functools.partial(kernels, scale=scale, block=block, window=window),
                                  default=loops)


def _blocked_attention_fwd(q, k, v, scale, block, window):
    """Kept for the backward pass: the output and each row's log-sum-exp,
    never a tile. Both carry a name, whichever lowering made them: under a
    `jax.checkpoint` that saves `ATTN_OUT_NAME` and `ATTN_LSE_NAME` they are
    what the layer holds across the step, and the backward's second run of the
    layer makes `q`, `k`, `v` again (cheap projections) and not this forward.
    Under `attn_core`, or `attn_window` within a sliding `window`."""
    from jax.ad_checkpoint import checkpoint_name  # not an attribute of `jax`; an alias module of what `import jax` loaded

    with scope("attn_core") if window is None else scope("attn_window"):
        out, lse = _by_lowering(lm_attention.attention_fwd, loops_fwd, scale, block, window, q, k, v)
        out, lse = checkpoint_name(out, ATTN_OUT_NAME), checkpoint_name(lse, ATTN_LSE_NAME)
        return out, (q, k, v, out, lse)


def loops_fwd(q, k, v, scale, block, window=None):
    """ONE loop body for every tile: query block i meets key blocks 0..i (the
    causal prefix; the tiles above the diagonal are never formed) under a
    running row maximum and row sum. A `window` bounds the key blocks by
    `window_reach` and meets them from the diagonal back: every row sees
    itself in the diagonal tile, so no row's running maximum is still -inf
    when a tile beyond its window masks it whole."""
    b, h, seq, _ = q.shape

    def query_block(i, carry):
        out, lse = carry
        qi = _rows(q, i, block)

        def key_block(j, state):
            top, total, acc = state
            s = _tile_scores(qi, _rows(k, j, block), i * block, j * block, scale, window)
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
        start = (zeros - jnp.inf, zeros, jnp.zeros((b, h, block, v.shape[-1]), jnp.float32))
        if window is None:
            top, total, acc = lax.fori_loop(0, i + 1, key_block, start)
        else:
            tiles = jnp.minimum(i, window_reach(window, block)) + 1
            top, total, acc = lax.fori_loop(0, tiles, lambda t, state: key_block(i - t, state), start)
        out = lax.dynamic_update_slice_in_dim(out, (acc / total[..., None]).astype(out.dtype), i * block, axis=2)
        return out, lax.dynamic_update_slice_in_dim(lse, top + jnp.log(total), i * block, axis=2)

    return lax.fori_loop(0, seq // block, query_block, (
        jnp.zeros((b, h, seq, v.shape[-1]), v.dtype), jnp.zeros((b, h, seq), jnp.float32)))


def _blocked_attention_bwd(scale, block, window, kept, g):
    with scope("attn_core") if window is None else scope("attn_window"):
        return _by_lowering(lm_attention.attention_bwd, loops_bwd, scale, block, window, *kept, g)


def loops_bwd(q, k, v, out, lse, g, scale, block, window=None):
    """Each key block once: its dK and dV gather over the query blocks i >= j
    that see it (a `window`: up to j + `window_reach`), each tile's
    probabilities made again from the row's log-sum-exp; dQ is added into
    its rows as the tiles go by."""
    seq = q.shape[2]
    # sum_k P dP of every row, which the softmax's backward subtracts: it is g . out
    inner = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def key_block(j, carry):
        dq, dk, dv = carry
        kj, vj = _rows(k, j, block), _rows(v, j, block)

        def query_block(i, state):
            dq, dkj, dvj = state
            qi, gi = _rows(q, i, block), _rows(g, i, block)
            s = _tile_scores(qi, kj, i * block, j * block, scale, window)
            probs = jnp.exp(s - _rows(lse, i, block)[..., None])
            dvj = dvj + jnp.einsum("bhqk,bhqd->bhkd", probs.astype(g.dtype), gi, preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", gi, vj, preferred_element_type=jnp.float32)
            ds = (probs * (dp - _rows(inner, i, block)[..., None]) * scale).astype(q.dtype)
            dq = _add_rows(dq, jnp.einsum("bhqk,bhkd->bhqd", ds, kj, preferred_element_type=jnp.float32), i, block)
            dkj = dkj + jnp.einsum("bhqk,bhqd->bhkd", ds, qi, preferred_element_type=jnp.float32)
            return dq, dkj, dvj

        last = seq // block if window is None else jnp.minimum(j + window_reach(window, block) + 1, seq // block)
        dq, dkj, dvj = lax.fori_loop(j, last, query_block, (
            dq, jnp.zeros(kj.shape, jnp.float32), jnp.zeros(vj.shape, jnp.float32)))
        return (dq, lax.dynamic_update_slice_in_dim(dk, dkj.astype(dk.dtype), j * block, axis=2),
                lax.dynamic_update_slice_in_dim(dv, dvj.astype(dv.dtype), j * block, axis=2))

    dq, dk, dv = lax.fori_loop(0, seq // block, key_block, (
        jnp.zeros(q.shape, jnp.float32), jnp.zeros_like(k), jnp.zeros_like(v)))
    return dq.astype(q.dtype), dk, dv


_blocked_attention.defvjp(_blocked_attention_fwd, _blocked_attention_bwd)


def causal_attention(q: Array, k: Array, v: Array, *, scale: float, block: int | None = None,
                     window: int | None = None) -> Array:
    """Causal softmax(q k^T * scale) v, float32 softmax, a tile of `block`
    query rows by `block` key rows at a time and never one kept, forward and
    backward (the backward recomputes each tile): in the fused TPU kernels of
    ops/lm_attention.py where the shapes fit them (q, k and v filled with
    zero channels to a multiple of 128 where that is all they lack:
    `lm_attention.fitting_dims`) and the step is lowered for a TPU, else in
    the loops over tiles. q, k (B, S, H, D); v (B, S, H, Dv) -> (B, S, H,
    Dv). A `window`: key position k is visible to query position q where
    q - window < k <= q, and only the tiles the window reaches are met (the
    window's own kernels, or the loops with its bounds, under `attn_window`);
    None is the causal core under `attn_core`."""
    seq = q.shape[1]
    block = min(block or ATTN_BLOCK, seq)
    if seq % block:
        raise ValueError(f"sequence length {seq} is not a multiple of the attention block {block}")
    if window is not None and window < 1:
        raise ValueError(f"a sliding window holds at least one position, not {window}")
    with scope("attn_core") if window is None else scope("attn_window"):
        v_dim = v.shape[-1]
        wide, v_wide = lm_attention.fitting_dims(seq, block, q.shape[-1], v_dim, q.dtype)
        fill = lambda x, width: jnp.pad(x, [(0, 0)] * 3 + [(0, width - x.shape[-1])])  # noqa: E731
        if wide != q.shape[-1]:  # zero channels, so that the kernels take the call (192 -> 256): exact
            q, k = fill(q, wide), fill(k, wide)
        if v_wide != v_dim:  # and v's (64 -> 128): the filled channels of the output are zeros, and are cut off
            v = fill(v, v_wide)
        q, k, v = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))  # heads lead: batch and head are the tiles' batch axes
        out = jnp.swapaxes(_blocked_attention(q, k, v, scale, block, window), 1, 2)
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
                  kv_heads: int | None = None, scale: float | None = None, window: int | None = None) -> Array:
    """Plain multi-head attention (`ouro`) and grouped-query attention
    (`granitemoehybrid`'s attention layers, `laguna`'s): q, k, v three
    projections, no bias, no q/k norm; `heads` query heads and `kv_heads`
    (None: as many) key and value heads of `head_dim` channels, query head i
    reading key/value head i // (heads / kv_heads); q and k rotated by the
    tables (all channels, or the first 2 x cos.shape[-1] of a partial rotary
    embedding: `apply_rope`), or not at all where `cos` is None; scores times
    `scale` (None: head_dim^-0.5). The core is :func:`causal_attention`, as
    for the latent archs, within a sliding `window` where one is given, with
    the key/value heads repeated before it (autodiff sums the copies'
    gradients back). Where `p` holds a `gate` (h, heads), each head's output
    is times sigmoid(x W_g) before `o` (a per-head output gate, "headwise"
    in arXiv:2505.06708), under `attn_gate`. x (B, S, h) -> (B, S, h)."""
    cd = x.dtype
    b, s, _ = x.shape
    kv_heads = kv_heads or heads
    with scope("attn_proj"):
        q, k, v = ((x @ p[name].astype(cd)).reshape(b, s, n, head_dim)
                   for name, n in (("q", heads), ("k", kv_heads), ("v", kv_heads)))
    if cos is not None:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if kv_heads != heads:
        with scope("attn_core") if window is None else scope("attn_window"):
            k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    out = causal_attention(q, k, v, scale=head_dim ** -0.5 if scale is None else scale, window=window)
    if "gate" in p:
        with scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.dot(x, p["gate"].astype(cd), preferred_element_type=jnp.float32))
            out = out * gate[..., None].astype(cd)
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


def route(router_w: Array, bias: Array | None, x: Array, *, top_k: int, scaling: float,
          scoring: str = "sigmoid_bias"):
    """Routing without groups. x (T, h) -> (expert ids (T, k), weights (T, k)
    float32, assignments per expert (E,) float32).

    `sigmoid_bias` (`noaux_tc`): scores = sigmoid(W_r x) in float32; the
    SELECTION is the top-k of scores + bias, the WEIGHTS are the selected
    scores themselves (no bias), divided by their sum, times `scaling`.
    `bias` is state, not a parameter: it gets no gradient. `softmax` (as
    Qwen2-MoE's `norm_topk_prob` router): scores = softmax(W_r x) over every
    expert in float32, the selection their top-k, the weights the selected
    scores divided by their sum, times `scaling`; no bias (None)."""
    if scoring not in ("sigmoid_bias", "softmax"):
        raise ValueError(f"router scoring {scoring!r} is not one of sigmoid_bias, softmax")
    with scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, ids = lax.top_k(scores, top_k)
        else:
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


def site_capacity(assignments: int, held: int, n_experts: int, scoring: str) -> int:
    """The rows a site's bounded branch works on: `capacity_rows`, and twice
    that under a `softmax` router. A router whose selection bias the load
    moves keeps the held share near its expectation; a softmax router holds
    no state, so a fresh model can leave some expert layer's held share past
    twice its expectation, and that site then takes the full-length branch
    for as long as the load stays there. The wider bound gathers and sums
    back the extra rows on every step (PERF.md weighs the two costs)."""
    return capacity_rows(assignments, held, n_experts) * (2 if scoring == "softmax" else 1)


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


def expert_layer(p: dict, bias: Array | None, x: Array, *, top_k: int, scaling: float, held: int,
                 share_index: int, scoring: str = "sigmoid_bias"):
    """The routed experts of ONE expert-parallel share: routes every token
    over ALL experts (`p["router"]` keeps its published width) and returns the
    sum, over the selected experts that this share holds (ids
    ``[share_index * held, (share_index + 1) * held)``; `p["experts"]` holds
    exactly those), of weight * expert(token). The shared expert is not in
    here. x (B, S, h) -> (y (B, S, h), load over all experts (E,), counters,
    the selected expert ids (B * S, top_k)). `scoring` and `bias`: :func:`route`'s.
    """
    b, s, h = x.shape
    tokens = b * s
    xf = x.reshape(tokens, h)
    ids, weights, load = route(p["router"], bias, xf, top_k=top_k, scaling=scaling, scoring=scoring)
    first = share_index * held
    with scope("moe_dispatch"):
        flat = ids.reshape(-1)
        here = (flat >= first) & (flat < first + held)
        # held experts first, grouped by expert; the rest, which no group covers, last
        order = jnp.argsort(jnp.where(here, flat - first, held), stable=True)
        inverse = jnp.argsort(order)
        group_sizes = load[first:first + held].astype(jnp.int32)
    y, dropped, bounded = _held_experts(top_k, site_capacity(tokens * top_k, held, load.shape[0], scoring),
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
