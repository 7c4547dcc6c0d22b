"""The Pallas/Mosaic kernels behind `ops/lm_mamba.py`'s chunked SSD: a pair
(forward, backward) for each chunk's output from its start state, a pair for
each chunk's own contribution to the state at its end (:func:`_own_kernel`,
the same decays to the chunk's last row), and the calls that build them.
Imported only from inside `lm_mamba.own_fwd/own_bwd/chunk_fwd/chunk_bwd`, that
is while the `tpu` branch of a fitting Mamba-2 site is traced (or a test asks
for interpret mode): the Pallas import costs 1.2-1.5 s on the chip's host and
a step with no fitting Mamba-2 layer must not pay it (PERF.md).

**What a kernel makes.** One program instance is one chunk of one sequence
and `group` of its heads. Its operands are plain blocks of the arrays as the
mixer holds them, positions in sublanes and channels in lanes: C and B
(chunk x state), x (chunk x heads * head_dim, a head a band of `head_dim`
lanes), Delta and the in-chunk cumulative log decay G (chunk x heads, and G
once more as heads x chunk, so that a head's G is a row as well as a
column), the chunk's start state (heads x head_dim x state) and D a lane.
The forward makes, for each head, in VMEM::

    y_t = sum_{s<=t} e^{G_t - G_s} (C_t . B_s) Delta_s x_s + e^{G_t} C_t . S_0 + D x_t

the scores `C B^T` once a chunk for every head (kept in scratch across the
head groups), a head's decays and weights (float32, cast to the compute
dtype) and their product with that head's `Delta x` on the MXU, and writes y
once, as (B, S, H * P) in the compute dtype: the layout the gated norm reads.

The backward makes the same decays and weights again, TRANSPOSED (rows s,
lanes t: every product it needs is then a plain or a `b^T` matmul), and
writes dx (Delta's and D's share of it), dDelta, dG (the decays' part as row
and column sums of dW o W, in two layouts that the caller adds), dC and dB
(the scores' cotangent summed over the heads in scratch first), the start
state's gradient and D's as per-chunk lane sums.

**No decay is clamped**: a decay is `exp` of a DIFFERENCE of one cumulative
sum, masked above the diagonal BEFORE the `exp` (:func:`decays`); written as
`e^{G_t} e^{-G_s}`, or masked by a product after the `exp`, it is inf x 0 for
in-chunk sums past -88, which a chunk of 256 reaches in every run of the
cell. `e^{G_t}` alone only underflows, to the 0 its true value rounds to.

**Heads in bands.** A 128-lane band holds `128 // head_dim` heads. A head's
work takes the whole band with the other heads' lanes zeroed in one operand,
so its product lands in its own lanes and the band's products add: at 64
channels the MXU does twice the work a head needs, which its 128 columns
would leave idle anyway.

Precision is `ops/lm_mamba.py`'s: matmul operands in the compute dtype (one
MXU pass) with float32 accumulation; Delta, G, every `exp`, the decays,
weights before their cast and the states' products in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lm_mamba import SSD_BAND as BAND, SSD_VMEM_BYTES

VMEM_LIMIT_BYTES = 2 * SSD_VMEM_BYTES

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b
_F32 = {"preferred_element_type": jnp.float32}


def decays(later, earlier, keep):
    """e^{later - earlier} where `keep`, else 0: the exponent is masked BEFORE
    the `exp`, so it is at most 0 wherever it is taken (module docstring)."""
    return jnp.exp(jnp.where(keep, later - earlier, -jnp.inf))


def _column(ref, lane):
    """Lane `lane` of a (rows, H) block as a column (rows, 1): exact (one term)."""
    block = ref[...]
    return jnp.sum(jnp.where(lax.broadcasted_iota(jnp.int32, block.shape, 1) == lane, block, 0.0), axis=1, keepdims=True)


def _fwd_kernel(c_ref, b_ref, g_ref, gt_ref, delta_ref, x_ref, d_ref, s0_ref, y_ref, scores_ref, *, width):
    chunk, state = c_ref.shape
    group = gt_ref.shape[0]
    per_band = BAND // width
    first = pl.program_id(2) * group
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():  # C B^T once a chunk: every head group of it reads the same scores
        scores_ref[...] = lax.dot_general(c_ref[...], b_ref[...], _NT, **_F32)

    below = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    head_of_lane = lax.broadcasted_iota(jnp.int32, (chunk, BAND), 1) // width

    def band(j, carry):
        lanes = pl.ds(pl.multiple_of(j * BAND, BAND), BAND)
        x = x_ref[:, lanes].astype(jnp.float32)
        starts = s0_ref[pl.ds(j * per_band, per_band)].reshape(BAND, state)
        state_in = lax.dot_general(c_ref[...], starts, _NT, **_F32)  # [t, (h, p)] = C_t . S_0[h, p]
        y = d_ref[:, lanes] * x
        for i in range(per_band):
            h = j * per_band + i
            big_g = _column(g_ref, first + h)  # (C, 1): G_t
            mine = head_of_lane == i
            dx = jnp.where(mine, x * _column(delta_ref, first + h), 0.0).astype(cd)  # this head's Delta x, other lanes 0
            weights = (scores_ref[...] * decays(big_g, gt_ref[pl.ds(h, 1), :], below)).astype(cd)
            y = y + jnp.dot(weights, dx, **_F32) + jnp.where(mine, jnp.exp(big_g), 0.0) * state_in
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, group // per_band, band, 0)


def _bwd_kernel(c_ref, b_ref, g_ref, gt_ref, delta_ref, x_ref, d_ref, s0_ref, dy_ref,
                dx_ref, ddelta_ref, dg_ref, dgt_ref, dc_ref, db_ref, ds0_ref, dd_ref,
                scores_ref, dscores_ref, dc_state_ref, *, width):
    chunk, state = c_ref.shape
    group = gt_ref.shape[0]
    per_band = BAND // width
    first = pl.program_id(2) * group
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        scores_ref[...] = lax.dot_general(b_ref[...], c_ref[...], _NT, **_F32)  # TRANSPOSED: [s, t] = B_s . C_t
        dscores_ref[...] = jnp.zeros_like(dscores_ref)
        dc_state_ref[...] = jnp.zeros_like(dc_state_ref)

    # rows s, lanes t: the pairs s <= t
    later = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    head_of_lane = lax.broadcasted_iota(jnp.int32, (chunk, BAND), 1) // width
    lane_of_head = lax.broadcasted_iota(jnp.int32, dg_ref.shape, 1)

    def band(j, carry):
        lanes = pl.ds(pl.multiple_of(j * BAND, BAND), BAND)
        x = x_ref[:, lanes].astype(jnp.float32)
        dy = dy_ref[:, lanes].astype(jnp.float32)
        starts = s0_ref[pl.ds(j * per_band, per_band)].reshape(BAND, state)
        state_in = lax.dot_general(c_ref[...], starts, _NT, **_F32)
        d_dx = jnp.zeros((chunk, BAND), jnp.float32)
        rates = jnp.zeros((chunk, BAND), jnp.float32)
        into = jnp.zeros((chunk, BAND), jnp.float32)
        for i in range(per_band):
            h = j * per_band + i
            big_g, rate = _column(g_ref, first + h), _column(delta_ref, first + h)  # (C, 1): G_s, Delta_s
            mine = head_of_lane == i
            dx_h = jnp.where(mine, x * rate, 0.0).astype(cd)
            dy_h = jnp.where(mine, dy, 0.0).astype(cd)
            decay_t = decays(gt_ref[pl.ds(h, 1), :], big_g, later)  # [s, t] = e^{G_t - G_s}
            weights_t = scores_ref[...] * decay_t
            d_dx_h = jnp.dot(weights_t.astype(cd), dy_h, **_F32)  # d(Delta x)_s = sum_t W_ts dy_t, in this head's lanes
            d_w_t = lax.dot_general(dx_h, dy_h, _NT, **_F32)  # [s, t] = dW_ts = dy_t . (Delta x)_s
            dscores_ref[...] += d_w_t * decay_t
            both = d_w_t * weights_t  # dW o W, transposed: + its sums over s to G_t, - its sums over t to G_s
            dgt_ref[pl.ds(h, 1), :] = jnp.sum(both, axis=0, keepdims=True)
            e = jnp.exp(big_g)
            to_state = jnp.sum(jnp.where(mine, dy * state_in, 0.0), axis=1, keepdims=True)
            column = e * to_state - jnp.sum(both, axis=1, keepdims=True)
            dg_ref[...] = jnp.where(lane_of_head == first + h, column, dg_ref[...])
            ddelta_ref[...] = jnp.where(lane_of_head == first + h, jnp.sum(d_dx_h * x, axis=1, keepdims=True), ddelta_ref[...])
            d_dx, rates, into = d_dx + d_dx_h, jnp.where(mine, rate, rates), jnp.where(mine, e, into)
        dx_ref[:, lanes] = (d_dx * rates + d_ref[:, lanes] * dy).astype(dx_ref.dtype)
        into_dy = (into * dy).astype(cd)  # e^{G_t} dy_t, head by head
        ds0_ref[pl.ds(j * per_band, per_band)] = lax.dot_general(into_dy, c_ref[...], _TN, **_F32).reshape(
            per_band, width, state).astype(ds0_ref.dtype)
        dc_state_ref[...] += jnp.dot(into_dy, starts, **_F32)
        dd_ref[:, lanes] = jnp.sum(dy * x, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, group // per_band, band, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():  # the scores' cotangent, every head's summed: dC_t = sum_s dSc_ts B_s, dB_s = sum_t dSc_ts C_t
        d_scores_t = dscores_ref[...].astype(cd)
        dc_ref[...] = (dc_state_ref[...] + lax.dot_general(d_scores_t, b_ref[...], _TN, **_F32)).astype(dc_ref.dtype)
        db_ref[...] = jnp.dot(d_scores_t, c_ref[...], **_F32).astype(db_ref.dtype)


def _own_kernel(g_ref, delta_ref, x_ref, b_ref, own_ref, *, width):
    chunk, state = b_ref.shape
    group = own_ref.shape[0]
    per_band = BAND // width
    first = pl.program_id(2) * group
    cd = x_ref.dtype
    head_of_lane = lax.broadcasted_iota(jnp.int32, (chunk, BAND), 1) // width

    def band(j, carry):
        lanes = pl.ds(pl.multiple_of(j * BAND, BAND), BAND)
        x = x_ref[:, lanes].astype(jnp.float32)
        written = jnp.zeros((chunk, BAND), jnp.float32)
        for i in range(per_band):
            big_g = _column(g_ref, first + j * per_band + i)
            dx = (x * _column(delta_ref, first + j * per_band + i)).astype(cd).astype(jnp.float32)
            written = jnp.where(head_of_lane == i, dx * jnp.exp(big_g[chunk - 1:] - big_g), written)  # decayed to the end
        own = lax.dot_general(written.astype(cd), b_ref[...], _TN, **_F32)  # [(h, p), k] = sum_s written_s B_s
        own_ref[pl.ds(j * per_band, per_band)] = own.reshape(per_band, width, state)
        return carry

    lax.fori_loop(0, group // per_band, band, 0)


def _own_bwd_kernel(g_ref, delta_ref, x_ref, b_ref, down_ref, dx_ref, ddelta_ref, dg_ref, db_ref, db_acc_ref, *, width):
    chunk, state = b_ref.shape
    group = down_ref.shape[0]
    per_band = BAND // width
    first = pl.program_id(2) * group
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)

    head_of_lane = lax.broadcasted_iota(jnp.int32, (chunk, BAND), 1) // width
    lane_of_head = lax.broadcasted_iota(jnp.int32, dg_ref.shape, 1)
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1

    def band(j, carry):
        lanes = pl.ds(pl.multiple_of(j * BAND, BAND), BAND)
        x = x_ref[:, lanes].astype(jnp.float32)
        d_own = down_ref[pl.ds(j * per_band, per_band)].reshape(BAND, state).astype(cd)
        d_written = lax.dot_general(b_ref[...], d_own, _NT, **_F32)  # [s, (h, p)] = B_s . d_own[h, p]
        d_x = jnp.zeros((chunk, BAND), jnp.float32)
        written = jnp.zeros((chunk, BAND), jnp.float32)
        for i in range(per_band):
            head = first + j * per_band + i
            big_g, rate = _column(g_ref, head), _column(delta_ref, head)
            mine = head_of_lane == i
            tail = jnp.exp(big_g[chunk - 1:] - big_g)
            dx = (x * rate).astype(cd).astype(jnp.float32)
            d_dx = jnp.where(mine, d_written * tail, 0.0)
            # G_C - G_s: the tail's exponent; + to the chunk's last row, - to row s
            to_tail = tail * jnp.sum(jnp.where(mine, d_written * dx, 0.0), axis=1, keepdims=True)
            column = jnp.where(last_row, jnp.sum(to_tail, axis=0, keepdims=True), 0.0) - to_tail
            dg_ref[...] = jnp.where(lane_of_head == head, column, dg_ref[...])
            ddelta_ref[...] = jnp.where(lane_of_head == head, jnp.sum(d_dx * x, axis=1, keepdims=True), ddelta_ref[...])
            d_x = d_x + d_dx * rate
            written = jnp.where(mine, dx * tail, written)
        dx_ref[:, lanes] = d_x.astype(dx_ref.dtype)
        db_acc_ref[...] += jnp.dot(written.astype(cd), d_own, **_F32)  # dB_s = sum_(h, p) written_s d_own[h, p]
        return carry

    lax.fori_loop(0, group // per_band, band, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        db_ref[...] = db_acc_ref[...].astype(db_ref.dtype)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _specs(chunk, heads, width, state, group):
    """Blocks of one program instance (b, n, g): chunk n of sequence b, heads g * group onwards."""
    by_chunk = lambda lanes: pl.BlockSpec((None, chunk, lanes), lambda b, n, g: (b, n, 0))  # noqa: E731 - resident across g
    return {
        "cb": by_chunk(state), "heads": by_chunk(heads),
        "rows": pl.BlockSpec((None, group, chunk), lambda b, n, g: (b, g, n)),
        "band": pl.BlockSpec((None, chunk, group * width), lambda b, n, g: (b, n, g)),
        "d": pl.BlockSpec((1, group * width), lambda b, n, g: (0, g)),
        "state": pl.BlockSpec((None, None, group, width, state), lambda b, n, g: (n, b, g, 0, 0)),
        "sums": pl.BlockSpec((None, None, 1, group * width), lambda b, n, g: (b, n, 0, g)),
    }


def _operands(x, delta, big_g, b, c, d_skip, width):
    """The operands as the kernels read them: G once more as (B, H, S), D a lane."""
    return c, b, big_g, jnp.swapaxes(big_g, 1, 2), delta, x, jnp.repeat(d_skip.astype(jnp.float32), width)[None]


def fwd_call(x, delta, big_g, b, c, starts, d_skip, width: int, group: int, interpret: bool = False):
    """x (B, S, H * P) in the compute dtype; delta, big_g (B, S, H) float32;
    b, c (B, S, K) in the compute dtype; starts (N, B, H, P, K) in the compute
    dtype; d_skip (H,) -> y (B, S, H * P) in x's dtype."""
    batch, seq, features = x.shape
    n, _, heads, _, state = starts.shape
    chunk = seq // n
    s = _specs(chunk, heads, width, state, group)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width),
        grid=(batch, n, heads // group),
        in_specs=[s["cb"], s["cb"], s["heads"], s["rows"], s["heads"], s["band"], s["d"], s["state"]],
        out_specs=s["band"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_chunk_fwd",
    )(*_operands(x, delta, big_g, b, c, d_skip, width), starts)


def bwd_call(x, delta, big_g, b, c, starts, d_skip, dy, width: int, group: int, interpret: bool = False):
    """The forward's operands and the cotangent of y -> (dx, ddelta, dG by
    columns (B, S, H), dG by rows (B, H, S), dc, db, dstarts, dD per chunk
    (B, N, 1, H * P) float32)."""
    batch, seq, features = x.shape
    n, _, heads, _, state = starts.shape
    chunk = seq // n
    s = _specs(chunk, heads, width, state, group)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width),
        grid=(batch, n, heads // group),
        in_specs=[s["cb"], s["cb"], s["heads"], s["rows"], s["heads"], s["band"], s["d"], s["state"], s["band"]],
        out_specs=[s["band"], s["heads"], s["heads"], s["rows"], s["cb"], s["cb"], s["state"], s["sums"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), f32(batch, seq, heads), f32(batch, seq, heads),
                   f32(batch, heads, seq), jax.ShapeDtypeStruct(c.shape, c.dtype), jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(starts.shape, starts.dtype), f32(batch, n, 1, features)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32), pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((chunk, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_chunk_bwd",
    )(*_operands(x, delta, big_g, b, c, d_skip, width), starts, dy)


def own_call(x, delta, big_g, b, chunk: int, width: int, group: int, interpret: bool = False):
    """x (B, S, H * P) in the compute dtype; delta, big_g (B, S, H) float32; b
    (B, S, K) in the compute dtype -> each chunk's own contribution to the
    state at its end, (N, B, H, P, K) float32."""
    batch, seq, features = x.shape
    heads, state, n = delta.shape[-1], b.shape[-1], seq // chunk
    s = _specs(chunk, heads, width, state, group)
    return pl.pallas_call(
        functools.partial(_own_kernel, width=width),
        grid=(batch, n, heads // group),
        in_specs=[s["heads"], s["heads"], s["band"], s["cb"]],
        out_specs=s["state"],
        out_shape=jax.ShapeDtypeStruct((n, batch, heads, width, state), jnp.float32),
        compiler_params=_params(), interpret=interpret, name="ssd_own_fwd",
    )(big_g, delta, x, b)


def own_bwd_call(x, delta, big_g, b, d_own, width: int, group: int, interpret: bool = False):
    """own_call's operands and the cotangent of its result -> (dx, ddelta,
    dG, db) like x, delta, big_g, b."""
    batch, seq, features = x.shape
    n, _, heads, _, state = d_own.shape
    chunk = seq // n
    s = _specs(chunk, heads, width, state, group)
    return pl.pallas_call(
        functools.partial(_own_bwd_kernel, width=width),
        grid=(batch, n, heads // group),
        in_specs=[s["heads"], s["heads"], s["band"], s["cb"], s["state"]],
        out_specs=[s["band"], s["heads"], s["heads"], s["cb"]],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (x, delta, big_g, b)],
        scratch_shapes=[pltpu.VMEM((chunk, state), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="ssd_own_bwd",
    )(big_g, delta, x, b, d_own)
