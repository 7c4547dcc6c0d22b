"""The two Pallas/Mosaic kernels behind `ops/lm_attention.py`, and the calls
that build them. THE ONE MODULE OF THE TOKEN FAMILY THAT IMPORTS PALLAS, and
itself imported only from inside `lm_attention.attention_fwd/attention_bwd`,
that is while the `tpu` branch of a fitting attention site is traced: the
import costs 1.2-1.5 s on the chip's host, `train/steps.py` is imported by
every process, and a step with no attention in it must not pay it
(`tests/test_lm.py` pins it; PERF.md, PR 28 + 29).

Operands come features-leading, (B, H * D, S) (`lm_attention.features_lead`),
so every tile is TRANSPOSED, keys in rows and queries in lanes: a query's
statistics (running maximum, sum, log-sum-exp, `sum(dO * O)`) are (1, block)
rows that broadcast along sublanes, and the softmax's reductions run over
sublanes.

One program instance is one (batch, head) and one block of the sequence; the
other operands' whole sequence is resident in VMEM (256 x 8,192 bfloat16 = 4
MiB), fetched once a (batch, head), and the tiles are met by a loop INSIDE the
kernel whose bounds are the causal prefix: tiles above the diagonal are
neither computed nor fetched, and only the diagonal tile pays the mask.

- forward: query block i meets key blocks 0..i under a running softmax, two
  tiles an iteration. Its two matmuls a tile are K Q^T and V^T P^T; K's rows
  lead in a VMEM scratch transposed once a (batch, head).
- backward, ONE pass of 5 matmuls a tile, none with a transposed left
  operand: key block j meets query blocks j..last; `dK`, `dV` of the block
  gather in VMEM; `dQ` of the whole (batch, head) gathers in a float32 VMEM
  scratch across the key blocks and is written once, in the compute dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the kernels may take (the default is 16 MiB of a v5e's 128). At 8,192 x 256 in bfloat16
# and tiles of 512 x 512 (ops/lm.py ATTN_BLOCK) the backward holds q and dO (4 MiB each,
# double-buffered), dQ in float32 (8) and its output block (4, double-buffered), and a few tiles of
# 1 MiB: ~40 MiB; the forward k, v (double-buffered) and k's rows.
VMEM_LIMIT_BYTES = 96 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))  # a @ b^T


def _causal(block):
    """The diagonal tile's mask, keys in rows and queries in lanes: key index <= query index."""
    tile = (block, block)
    return lax.broadcasted_iota(jnp.int32, tile, 0) <= lax.broadcasted_iota(jnp.int32, tile, 1)


def _lanes(i, block):
    return pl.ds(pl.multiple_of(i * block, block), block)


def _fwd_kernel(q_ref, k_ref, v_ref, out_ref, lse_ref, rows_ref, top_ref, total_ref, acc_ref, *, scale, block):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():  # the keys of this (batch, head) with their rows leading, once: QK^T's left operand
        rows_ref[...] = k_ref[...].T

    q = q_ref[...]
    top_ref[...] = jnp.full(top_ref.shape, -jnp.inf, jnp.float32)
    total_ref[...] = jnp.zeros(total_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def scores(j):
        return jnp.dot(rows_ref[_lanes(j, block), :], q, preferred_element_type=jnp.float32) * scale  # (keys, queries)

    def meet(j, s):
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(s, axis=0, keepdims=True))
        weights = jnp.exp(s - new_top)
        keep = jnp.exp(top - new_top)
        top_ref[...] = new_top
        total_ref[...] = total_ref[...] * keep + jnp.sum(weights, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jnp.dot(v_ref[:, _lanes(j, block)], weights.astype(v_ref.dtype),
                                                     preferred_element_type=jnp.float32)

    def pair(jj, _):
        # both tiles' scores ahead of either softmax: the second QK^T runs on the MXU under the
        # first tile's exp (forward 10.31 -> 9.56 ms a layer on a v5e; PERF.md, PR 28 + 29)
        first, second = scores(2 * jj), scores(2 * jj + 1)
        meet(2 * jj, first)
        meet(2 * jj + 1, second)

    lax.fori_loop(0, i // 2, pair, None)

    @pl.when(i % 2 == 1)
    def _():
        meet(i - 1, scores(i - 1))

    meet(i, jnp.where(_causal(block), scores(i), -jnp.inf))
    total = total_ref[...]
    out_ref[...] = (acc_ref[...] / total).astype(out_ref.dtype)
    lse_ref[...] = top_ref[...] + jnp.log(total)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, inner_ref, dq_ref, dk_ref, dv_ref,
                dq_acc, dk_acc, dv_acc, *, scale, block):
    j = pl.program_id(2)
    blocks = pl.num_programs(2)
    k, v = k_ref[...], v_ref[...]
    k_rows, v_rows = k.T, v.T  # rows leading, once a key block: the left operands of K Q^T and V dO^T

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def tile(i, diagonal):
        lanes = _lanes(i, block)
        q, g = q_ref[:, lanes], g_ref[:, lanes]
        s = jnp.dot(k_rows, q, preferred_element_type=jnp.float32) * scale  # (keys, queries)
        if diagonal:
            s = jnp.where(_causal(block), s, -jnp.inf)
        probs = jnp.exp(s - lse_ref[i])
        dv_acc[...] += lax.dot_general(g, probs.astype(g.dtype), _NT, preferred_element_type=jnp.float32)
        dp = jnp.dot(v_rows, g, preferred_element_type=jnp.float32)
        ds = (probs * (dp - inner_ref[i]) * scale).astype(q.dtype)
        dk_acc[...] += lax.dot_general(q, ds, _NT, preferred_element_type=jnp.float32)
        dq_acc[:, lanes] += jnp.dot(k, ds, preferred_element_type=jnp.float32)

    tile(j, True)
    lax.fori_loop(j + 1, blocks, lambda i, _: tile(i, False), None)
    dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == blocks - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _whole(dim, seq):
    """One (batch, head)'s whole sequence, resident across the blocks."""
    return pl.BlockSpec((None, dim, seq), lambda b, h, i: (b, h, 0))


def _block(dim, block):
    return pl.BlockSpec((None, dim, block), lambda b, h, i: (b, h, i))


def fwd_call(q, k, v, heads: int, scale: float, block: int, interpret: bool = False, window: int | None = None):
    """The forward kernel on features-leading operands: q, k (B, H * D, S),
    v (B, H * Dv, S) -> (out (B, H * Dv, S), lse (B, H, S / block, 1, block)).
    A `window`: the window's kernel, `window_attention_fwd` (below)."""
    b, features, seq = q.shape
    d, dv = features // heads, v.shape[1] // heads
    blocks = seq // block
    kernel = (functools.partial(_fwd_kernel, scale=scale, block=block) if window is None
              else functools.partial(_window_fwd_kernel, scale=scale, block=block, window=window))
    return pl.pallas_call(
        kernel,
        grid=(b, heads, blocks),
        in_specs=[_block(d, block), _whole(d, seq), _whole(dv, seq)],
        out_specs=[_block(dv, block), pl.BlockSpec((None, None, None, 1, block), lambda b, h, i: (b, h, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct((b, heads, blocks, 1, block), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((seq, d), k.dtype), pltpu.VMEM((1, block), jnp.float32),
                        pltpu.VMEM((1, block), jnp.float32), pltpu.VMEM((dv, block), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="causal_attention_fwd" if window is None else "window_attention_fwd",
    )(q, k, v)


def bwd_call(q, k, v, g, lse, inner, heads: int, scale: float, block: int, interpret: bool = False,
             window: int | None = None):
    """The backward kernel on features-leading operands and the output's
    cotangent g (B, H * Dv, S); lse and inner = sum(dO * O) over a row, both
    (B, H, S / block, 1, block) float32 -> (dq, dk, dv) like q, k, v. A
    `window`: the window's kernel, `window_attention_bwd` (below)."""
    b, features, seq = q.shape
    d, dv = features // heads, v.shape[1] // heads
    blocks = seq // block
    stats = pl.BlockSpec((None, None, blocks, 1, block), lambda b, h, j: (b, h, 0, 0, 0))
    kernel = (functools.partial(_bwd_kernel, scale=scale, block=block) if window is None
              else functools.partial(_window_bwd_kernel, scale=scale, block=block, window=window))
    return pl.pallas_call(
        kernel,
        grid=(b, heads, blocks),
        in_specs=[_whole(d, seq), _block(d, block), _block(dv, block), _whole(dv, seq), stats, stats],
        out_specs=[_whole(d, seq), _block(d, block), _block(dv, block)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((d, seq), jnp.float32), pltpu.VMEM((d, block), jnp.float32),
                        pltpu.VMEM((dv, block), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
        name="causal_attention_bwd" if window is None else "window_attention_bwd",
    )(q, k, v, g, lse, inner)


# ---- within a sliding window (ops/lm.py `causal_attention(..., window=W)`) --------------------------------------
#
# The same layout, operands, calls (`fwd_call` / `bwd_call` with a `window`) and precision as the causal pair, named
# apart (`window_attention_fwd` / `_bwd`) so that a trace tells them from it. Key position k is visible to query
# position q where q - W < k <= q, so query block i meets key blocks i - reach..i and key block j query blocks
# j..j + reach (reach = ceil((W - 1) / block), `ops.lm.window_reach`: 1 where W = block), and of those only the
# diagonal tile and the tiles the window's edge crosses pay a mask: the `whole` blocks nearest the diagonal
# (floor((W - block) / block), none where W <= block) lie inside the window entirely. The forward meets the diagonal
# tile FIRST: every query sees itself there, so no query's running maximum is still -inf when an edge tile masks its
# whole column.


def _window_bounds(window: int, block: int) -> tuple[int, int]:
    """(reach, whole): the key blocks before the diagonal that the window reaches (the loops' bound,
    `ops.lm.window_reach`), and how many of the nearest of them it covers entirely."""
    from .lm import window_reach  # the module that imports this one's caller, loaded before any kernel is built

    return window_reach(window, block), max(0, (window - block) // block)


def _in_window(block, window, gap):
    """A tile's mask, keys in rows and queries in lanes, `gap` = (query block - key block) x block: visible where
    0 <= query index - key index < window."""
    tile = (block, block)
    apart = gap + lax.broadcasted_iota(jnp.int32, tile, 1) - lax.broadcasted_iota(jnp.int32, tile, 0)
    return (apart >= 0) & (apart < window)


def _window_fwd_kernel(q_ref, k_ref, v_ref, out_ref, lse_ref, rows_ref, top_ref, total_ref, acc_ref, *, scale, block,
                       window):
    i = pl.program_id(2)
    reach, whole = _window_bounds(window, block)

    @pl.when(i == 0)
    def _():  # as the causal kernel's
        rows_ref[...] = k_ref[...].T

    q = q_ref[...]
    top_ref[...] = jnp.full(top_ref.shape, -jnp.inf, jnp.float32)
    total_ref[...] = jnp.zeros(total_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def scores(j):
        return jnp.dot(rows_ref[_lanes(j, block), :], q, preferred_element_type=jnp.float32) * scale  # (keys, queries)

    def meet(j, masked):
        s = scores(j)
        if masked:
            s = jnp.where(_in_window(block, window, (i - j) * block), s, -jnp.inf)
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(s, axis=0, keepdims=True))
        weights = jnp.exp(s - new_top)
        keep = jnp.exp(top - new_top)
        top_ref[...] = new_top
        total_ref[...] = total_ref[...] * keep + jnp.sum(weights, axis=0, keepdims=True)
        acc_ref[...] = acc_ref[...] * keep + jnp.dot(v_ref[:, _lanes(j, block)], weights.astype(v_ref.dtype),
                                                     preferred_element_type=jnp.float32)

    meet(i, True)
    inside = jnp.maximum(i - whole, 0)
    lax.fori_loop(inside, i, lambda j, _: meet(j, False), None)
    lax.fori_loop(jnp.maximum(i - reach, 0), inside, lambda j, _: meet(j, True), None)
    total = total_ref[...]
    out_ref[...] = (acc_ref[...] / total).astype(out_ref.dtype)
    lse_ref[...] = top_ref[...] + jnp.log(total)


def _window_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, inner_ref, dq_ref, dk_ref, dv_ref,
                       dq_acc, dk_acc, dv_acc, *, scale, block, window):
    j = pl.program_id(2)
    blocks = pl.num_programs(2)
    reach, whole = _window_bounds(window, block)
    k, v = k_ref[...], v_ref[...]
    k_rows, v_rows = k.T, v.T  # as the causal kernel's

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def tile(i, masked):
        lanes = _lanes(i, block)
        q, g = q_ref[:, lanes], g_ref[:, lanes]
        s = jnp.dot(k_rows, q, preferred_element_type=jnp.float32) * scale  # (keys, queries)
        if masked:
            s = jnp.where(_in_window(block, window, (i - j) * block), s, -jnp.inf)
        probs = jnp.exp(s - lse_ref[i])
        dv_acc[...] += lax.dot_general(g, probs.astype(g.dtype), _NT, preferred_element_type=jnp.float32)
        dp = jnp.dot(v_rows, g, preferred_element_type=jnp.float32)
        ds = (probs * (dp - inner_ref[i]) * scale).astype(q.dtype)
        dk_acc[...] += lax.dot_general(q, ds, _NT, preferred_element_type=jnp.float32)
        dq_acc[:, lanes] += jnp.dot(k, ds, preferred_element_type=jnp.float32)

    tile(j, True)
    edge = jnp.minimum(j + whole + 1, blocks)
    lax.fori_loop(j + 1, edge, lambda i, _: tile(i, False), None)
    lax.fori_loop(edge, jnp.minimum(j + reach + 1, blocks), lambda i, _: tile(i, True), None)
    dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(j == blocks - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
