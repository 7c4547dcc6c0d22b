"""The Pallas/Mosaic kernels behind `ops/lm_kda.py`: two for the in-chunk
work, two for the scan over the chunks (after them), two for the short
convolutions (the end of this module; Mamba-2's xBC convolution,
ops/lm_mamba.py, takes them too, with a bias), and the calls that build them.
Imported only from inside `lm_kda.operands_fwd/operands_bwd`,
`lm_kda.scan_fwd/scan_bwd` and `lm_kda.conv_fwd/conv_bwd`, that is while
the `tpu` branch of a fitting KDA site is traced (or a test asks for interpret
mode): the Pallas import costs 1.2-1.5 s on the chip's host, `train/steps.py`
is imported by every process, and a step with no KDA layer must not pay it
(`tests/test_lm_kda.py` pins it; PERF.md, PR 28 + 29 + 36).

**What a kernel makes.** From q, k, v (compute dtype), g (float32) and beta,
as `kda_attention` holds them, (B, S, H * D) with a head a band of D lanes and
a chunk C rows, one program instance makes `CHUNKS_AT_ONCE` chunks of one
(sequence, head): `_state_scan`'s six operands, written in its chunk-leading
shapes (N, B, H, C, ...), and the most negative cumulative log decay met. The
backward kernel makes the same matrices AGAIN in VMEM and turns the six
cotangents into dq, dk, dv, dg, dbeta. Nothing of a chunk's intermediates
reaches HBM in either.

**No gate is clamped: every exponent is the log decay from an earlier row to a
later one, so at most 0.**
The plain form (`lm_kda._decayed_scores`) lets pairs of rows in different
16-row sub-blocks meet through a reference row, as a matmul, and multiplies
the pairs inside a sub-block out channel by channel. Here EVERY pair meets
through a reference, by halving: at the level of half-size h (1, 2, ..., C/2)
rows are cut into groups of 2h, and a row r of a group's later half meets a
row j of its earlier half through the later half's first row,

    e^{G_r - G_j} = e^{G_r - G_ref} e^{G_ref - G_j},   j < ref <= r,

both factors at most 1 whatever the gates. Each pair j < r belongs to exactly
one level, so A and B are log2(C) matmuls each, masked and joined
by selects; B's diagonal is the rowwise q . k. The cumulative sum G is ONE
matmul of a 0/1 triangular matrix (`cumulative`) with g, exact in float32
because g goes through the MXU as three bfloat16 parts whose sum it is; a
level's exponent of a row is G's difference to its group's reference row, a
broadcast along sublanes (`_decay_sums`), at most 0 in every row.

**The solve is an inverse by blocks.** T = (I + Diag(beta) A)^-1: the 16 x 16
diagonal blocks by the finite Neumann product (I - L)(I + L^2)(I + L^4)(I +
L^8) (L^16 = 0), then doubled twice, [[T11, 0], [-T22 L21 T11, T22]], all
as full matmuls under masks; `w`, `u0` = T times the right-hand sides. Every
value float32; every product three bfloat16 passes of the MXU (`_dot32`: the
product to 2^-16).

**Two chunks a matmul.** Every matmul above is 64 rows of a 128 x 128 MXU, so
the kernels work on TILES of `TILE_ROWS` = 128 rows, two chunks at once: the
masks already keep a pair inside its group, hence inside its chunk, A, B and T
come out block-diagonal by chunk, and the same number of MXU operations makes
twice the chunks. Only B's way out (and dB's way in) needs the blocks stacked:
a 0/1 matmul (`_fold`), exact.

Precision is the module docstring's of `ops/lm_kda.py`: matmul operands of the
decayed scores in the compute dtype with float32 accumulation; g, its sums,
every `exp`, the inverse, `w` and `u0` before their casts in float32 (the
inverse's products to 2^-16, above).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lm_kda import CONV_HALO

# Chunks one program instance makes (fewer where the sequence has fewer): amortises the ~0.35 us a grid step costs.
CHUNKS_AT_ONCE = 16
# The diagonal blocks the inverse starts from (the Neumann product below stops at L^15).
SOLVE_BLOCK = 16
# Rows of the tiles the matmuls work on: as many whole chunks as fill the MXU's 128 rows, block-diagonal by chunk.
TILE_ROWS = 128
VMEM_LIMIT_BYTES = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_F32 = {"preferred_element_type": jnp.float32}


def levels(chunk: int) -> list[int]:
    """The half-sizes at which pairs of a chunk's rows meet: 1, 2, ..., chunk / 2."""
    return [1 << i for i in range(chunk.bit_length() - 1)]


def cumulative(chunk: int, tile: int) -> np.ndarray:
    """The 0/1 matrix (T, T) whose product with a tile's g (T, K), T a whole
    number of chunks, is the cumulative sum of g inside each chunk: row r
    sums the rows t <= r of r's chunk."""
    r, t = np.arange(tile)[:, None], np.arange(tile)[None, :]
    return ((t <= r) & (r // chunk == t // chunk)).astype(np.float32)


def _level_masks(chunk, tile):
    """Per level, over a tile's rows (`meets`: row r is in a group's later half
    and column j in the same group's earlier half, so in r's chunk; `met`: its
    transpose, the same pairs seen from j's row)."""
    r = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    j = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)

    def level(i, later, earlier):
        return ((r >> (i + 1)) == (j >> (i + 1))) & (((later >> i) & 1) == 1) & (((earlier >> i) & 1) == 0)

    return [(level(i, r, j), level(i, j, r)) for i in range(len(levels(chunk)))]


def _split3(x):
    """float32 -> three bfloat16 arrays whose sum is x to float32's last bit."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(x.dtype)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(x.dtype)).astype(jnp.bfloat16)


def _sums(pattern, x):
    """pattern (R, T) of 0/1 in bfloat16 times x (T, K) float32, to float32 rounding."""
    hi, mid, lo = _split3(x)
    return (jnp.dot(pattern, lo, **_F32) + jnp.dot(pattern, mid, **_F32)) + jnp.dot(pattern, hi, **_F32)


def _split2(x):
    """float32 -> (hi, lo) in bfloat16 with hi + lo = x to 2^-16 of it."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(x.dtype)).astype(jnp.bfloat16)


def _dot32(a, b, transposed=False):
    """a @ b (a @ b^T if `transposed`) of float32 matrices, or of their
    `_split2` halves where a caller has them already, as THREE bfloat16 passes
    of the MXU, hi hi + hi lo + lo hi: the product to 2^-16, in float32. The
    six passes of `Precision.HIGHEST` read 17.3 / 26.5 ms a layer (forward /
    backward kernel) where these read 13.6 / 22.1, and moved no result by more
    than its bfloat16 rounding (PERF.md, PR 36)."""
    (a_hi, a_lo), (b_hi, b_lo) = (x if isinstance(x, tuple) else _split2(x) for x in (a, b))
    dot = (lambda x, y: lax.dot_general(x, y, _NT, **_F32)) if transposed else (lambda x, y: jnp.dot(x, y, **_F32))
    return (dot(a_lo, b_hi) + dot(a_hi, b_lo)) + dot(a_hi, b_hi)


def _unit_lower_inverse(low, chunk):
    """(I + low)^-1 for low (T, T) float32, strictly lower triangular inside
    each chunk's diagonal block and zero outside them (module docstring)."""
    tile = low.shape[0]
    r = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    j = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    same = lambda size: (r // size) == (j // size)  # noqa: E731
    block = min(SOLVE_BLOCK, chunk)
    power = -jnp.where(same(block), low, 0.0)
    inverse = jnp.where(r == j, 1.0, 0.0) + power
    for _ in range(block.bit_length() - 2):  # (I + N)(I + N^2)(I + N^4)...: every power below `block`
        halves = _split2(power)
        power = _dot32(halves, halves)
        inverse = inverse + _dot32(inverse, power)
    while block < chunk:
        across = jnp.where(same(2 * block) & ~same(block), low, 0.0)
        halves = _split2(inverse)
        inverse = inverse - _dot32(_dot32(halves, across), halves)
        block *= 2
    return inverse


def _block(n, tile):
    """Rows of block n of a scratch of (levels + 2) blocks of T rows (`_decay_sums`)."""
    return slice(n * tile, (n + 1) * tile)


def _decay_sums(sums_ref, pattern, g, chunk):
    """Every sum of log decays a tile's `exp`s take, into `sums_ref`, (levels
    + 2) blocks of T rows: block 0 the cumulative sum G (`pattern`:
    `cumulative`); block 1 + i, level i's exponent of each row, G's
    difference to its group's reference row (a later-half row r: G_r - G_ref;
    an earlier-half row j: G_ref - G_j); the last block `G_last - G_r` of
    the row's chunk. Each difference a broadcast along sublanes, each at most 0
    for g <= 0."""
    tile, width = g.shape
    big_g = _sums(pattern, g)
    sums_ref[_block(0, tile), :] = big_g
    position = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    for i, half in enumerate(levels(chunk)):
        groups = big_g.reshape(tile // (2 * half), 2 * half, width)
        diff = (groups - groups[:, half:half + 1, :]).reshape(tile, width)
        sums_ref[_block(1 + i, tile), :] = jnp.where(((position >> i) & 1) == 1, diff, -diff)
    chunks = big_g.reshape(tile // chunk, chunk, width)
    last = len(levels(chunk)) + 1
    sums_ref[_block(last, tile), :] = (chunks[:, chunk - 1:chunk, :] - chunks).reshape(tile, width)


def _decay_sums_bwd(dsums_ref, pattern_t, chunk, tile):
    """The transpose of `_decay_sums`: the cotangent of g (T, K) from those of
    the sums in `dsums_ref`."""
    width = dsums_ref.shape[1]
    d_big = dsums_ref[_block(0, tile), :]
    position = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    for i, half in enumerate(levels(chunk)):
        later = ((position >> i) & 1) == 1
        d_diff = dsums_ref[_block(1 + i, tile), :]
        d_diff = jnp.where(later, d_diff, -d_diff)
        to_ref = jnp.sum(d_diff.reshape(tile // (2 * half), 2 * half, width), axis=1, keepdims=True)
        at_ref = (position & (2 * half - 1)) == half
        spread = jnp.broadcast_to(to_ref, (tile // (2 * half), 2 * half, width)).reshape(tile, width)
        d_big = d_big + d_diff - jnp.where(at_ref, spread, 0.0)
    last = len(levels(chunk)) + 1
    d_out = dsums_ref[_block(last, tile), :]
    to_last = jnp.sum(d_out.reshape(tile // chunk, chunk, width), axis=1, keepdims=True)
    at_last = (position & (chunk - 1)) == chunk - 1
    spread = jnp.broadcast_to(to_last, (tile // chunk, chunk, width)).reshape(tile, width)
    d_big = d_big - d_out + jnp.where(at_last, spread, 0.0)
    return _sums(pattern_t, d_big)


def _beta_column(beta_ref, rows, head):
    """One head's write strengths of a tile as a column (T, 1), from the (rows, H) block."""
    tile = beta_ref[rows, :]
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == head, tile, 0.0), axis=1, keepdims=True)


def _fold(chunk, tile, dtype):
    """The 0/1 matrix (T, C) that stacks a block-diagonal (T, T)'s chunk blocks, (T, T) @ fold -> (T, C)."""
    return (lax.broadcasted_iota(jnp.int32, (tile, chunk), 0) % chunk
            == lax.broadcasted_iota(jnp.int32, (tile, chunk), 1)).astype(dtype)


def _made(q, k, v, beta, sums_ref, masks, chunk, cd):
    """One tile's matrices (block-diagonal by chunk) from its float32 q, k, v
    (T, K), beta (T, 1) and the sums of log decays in `sums_ref`
    (`_decay_sums`)."""
    tile = q.shape[0]
    into = jnp.exp(sums_ref[_block(0, tile), :])  # e^G: underflows to the 0 its true value rounds to
    a = jnp.zeros((tile, tile), jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    b = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    for i, (meets, _) in enumerate(masks):
        decay = jnp.exp(sums_ref[_block(1 + i, tile), :])
        ke, qe = (k * decay).astype(cd), (q * decay).astype(cd)
        a = jnp.where(meets, lax.dot_general(ke, ke, _NT, **_F32), a)
        b = jnp.where(meets, lax.dot_general(qe, ke, _NT, **_F32), b)
    inverse = _unit_lower_inverse(beta * a, chunk)
    halves = _split2(inverse)
    w = _dot32(halves, beta * (k * into))
    u0 = _dot32(halves, beta * v)
    out = jnp.exp(sums_ref[_block(1 + len(masks), tile), :])  # e^{G_last - G}
    return into, out, a, b, inverse, w, u0


def _rows(i, tile):
    return pl.ds(pl.multiple_of(i * tile, tile), tile)


def _store(ref, i, x, chunk):
    """A tile's rows (T, .) into a chunk-leading block (chunks, C, .), from chunk i * T / C on."""
    for c in range(x.shape[0] // chunk):
        ref[i * (x.shape[0] // chunk) + c] = x[c * chunk:(c + 1) * chunk].astype(ref.dtype)


def _load(ref, i, tile, chunk):
    """The tile's rows (T, .) in float32 out of a chunk-leading block (chunks, C, .)."""
    each = tile // chunk
    return jnp.concatenate([ref[i * each + c] for c in range(each)], axis=0).astype(jnp.float32)


def _fwd_kernel(pattern_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                qg_ref, b_ref, w_ref, u0_ref, kh_ref, gamma_ref, lowest_ref, sums_ref, *, chunk, tile, tiles):
    head = pl.program_id(2)
    cd = q_ref.dtype
    masks = _level_masks(chunk, tile)
    fold = _fold(chunk, tile, cd)

    def one(i, lowest):
        rows = _rows(i, tile)
        q, k, v = (ref[rows, :].astype(jnp.float32) for ref in (q_ref, k_ref, v_ref))
        _decay_sums(sums_ref, pattern_ref[...], g_ref[rows, :], chunk)
        into, out, _, b, _, w, u0 = _made(q, k, v, _beta_column(beta_ref, rows, head), sums_ref, masks, chunk, cd)
        _store(qg_ref, i, q * into, chunk)
        _store(b_ref, i, b if tile == chunk else jnp.dot(b.astype(cd), fold, **_F32), chunk)
        _store(w_ref, i, w, chunk)
        _store(u0_ref, i, u0, chunk)
        _store(kh_ref, i, k * out, chunk)
        for c in range(tile // chunk):
            gamma_ref[i * (tile // chunk) + c] = jnp.exp(sums_ref[(c + 1) * chunk - 1:(c + 1) * chunk, :])
        big_g = sums_ref[_block(0, tile), :]
        return jnp.minimum(lowest, jnp.min(jnp.min(big_g, axis=1, keepdims=True), axis=0, keepdims=True))

    lowest = lax.fori_loop(0, tiles, one, jnp.full((1, 1), jnp.inf, jnp.float32))
    at = ((lax.broadcasted_iota(jnp.int32, lowest_ref.shape, 0) == pl.program_id(1))
          & (lax.broadcasted_iota(jnp.int32, lowest_ref.shape, 1) == head))
    lowest_ref[...] = jnp.where(at, lowest, lowest_ref[...])


def _bwd_kernel(pattern_ref, pattern_t_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                dqg_ref, db_ref, dw_ref, du0_ref, dkh_ref, dgamma_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, sums_ref, dsums_ref, *, chunk, tile, tiles):
    head = pl.program_id(2)
    cd = q_ref.dtype
    masks = _level_masks(chunk, tile)
    row = lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    col = lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    below = (row > col) & (row // chunk == col // chunk)
    row_of = lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    unfold = _fold(chunk, tile, cd)

    def one(i, _):
        rows = _rows(i, tile)
        q, k, v = (ref[rows, :].astype(jnp.float32) for ref in (q_ref, k_ref, v_ref))
        beta = _beta_column(beta_ref, rows, head)
        _decay_sums(sums_ref, pattern_ref[...], g_ref[rows, :], chunk)
        into, out, a, _, inverse, w, u0 = _made(q, k, v, beta, sums_ref, masks, chunk, cd)
        dqg, dw, du0, dkh = (_load(ref, i, tile, chunk) for ref in (dqg_ref, dw_ref, du0_ref, dkh_ref))
        d_b = _load(db_ref, i, tile, chunk)  # (T, C): each chunk's block
        if tile != chunk:  # laid along the diagonal: every use below is under a mask of its own chunk
            d_b = lax.dot_general(d_b.astype(cd), unfold, _NT, **_F32)
        # the solve: X = T R  =>  dR = T^T dX,  d(beta A) = -dR X^T below the diagonal
        inverse_t = _split2(inverse.T)
        d_rw, d_ru = _dot32(inverse_t, dw), _dot32(inverse_t, du0)
        d_low = jnp.where(below, -(_dot32(d_rw, w, transposed=True) + _dot32(d_ru, u0, transposed=True)), 0.0)
        k_into = k * into
        d_beta = (jnp.sum(d_rw * k_into + d_ru * v, axis=1, keepdims=True) + jnp.sum(d_low * a, axis=1, keepdims=True))
        d_a = beta * d_low
        d_a_t, d_b_t = d_a.T, d_b.T
        on_diagonal = jnp.sum(jnp.where(row == col, d_b, 0.0), axis=1, keepdims=True)
        d_rw_beta = beta * d_rw
        dq = dqg * into + on_diagonal * k
        dk = d_rw_beta * into + dkh * out + on_diagonal * q
        # cotangents of the sums of log decays, block by block as `_decay_sums` lays them; gamma = e^G's last row of a chunk
        d_into = d_rw_beta * k + dqg * q
        for c in range(tile // chunk):
            d_into = d_into + jnp.where(row_of == (c + 1) * chunk - 1, dgamma_ref[i * (tile // chunk) + c], 0.0)
        dsums_ref[_block(0, tile), :] = d_into * into
        for n, (meets, met) in enumerate(masks):
            decay = jnp.exp(sums_ref[_block(1 + n, tile), :])
            ke, qe = (k * decay).astype(cd), (q * decay).astype(cd)
            d_ke = (jnp.dot((jnp.where(meets, d_a, 0.0) + jnp.where(met, d_a_t, 0.0)).astype(cd), ke, **_F32)
                    + jnp.dot(jnp.where(met, d_b_t, 0.0).astype(cd), qe, **_F32))
            d_qe = jnp.dot(jnp.where(meets, d_b, 0.0).astype(cd), ke, **_F32)
            dk = dk + d_ke * decay
            dq = dq + d_qe * decay
            dsums_ref[_block(1 + n, tile), :] = (d_ke * k + d_qe * q) * decay
        dsums_ref[_block(1 + len(masks), tile), :] = dkh * k * out
        dq_ref[rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[rows, :] = (beta * d_ru).astype(dv_ref.dtype)
        dg_ref[rows, :] = _decay_sums_bwd(dsums_ref, pattern_t_ref[...], chunk, tile)
        lane = lax.broadcasted_iota(jnp.int32, (tile, dbeta_ref.shape[1]), 1)
        dbeta_ref[rows, :] = jnp.where(lane == head, d_beta, dbeta_ref[rows, :])

    lax.fori_loop(0, tiles, one, None)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _cut(seq: int, chunk: int) -> tuple[int, int, int]:
    """(chunks a program instance makes, rows of a tile, program instances a
    sequence): as many chunks to a tile as fill the MXU's 128 rows."""
    n = seq // chunk
    at_once = max(c for c in (CHUNKS_AT_ONCE, 8, 4, 2, 1) if c <= CHUNKS_AT_ONCE and n % c == 0)
    together = max(c for c in (4, 2, 1) if c * chunk <= TILE_ROWS and at_once % c == 0)
    return at_once, together * chunk, n // at_once


def _specs(heads, width, chunk, at_once):
    band = pl.BlockSpec((None, at_once * chunk, width), lambda b, n, h: (b, n, h))  # (B, S, H * D): a head's lanes
    per_head = pl.BlockSpec((None, at_once * chunk, heads), lambda b, n, h: (b, n, 0))  # (B, S, H): resident across h
    leading = lambda *tile: pl.BlockSpec((at_once, None, None, *tile), lambda b, n, h: (n, b, h, 0, 0))  # noqa: E731
    whole = lambda shape: pl.BlockSpec(shape, lambda b, n, h: (0, 0))  # noqa: E731
    return band, per_head, leading, whole


def fwd_call(q, k, v, g, beta, heads: int, chunk: int, interpret: bool = False):
    """q, k, v (B, S, H * D) in the compute dtype, g alike in float32, beta (B,
    S, H) float32 -> (qg, b, w, u0, kh (N, B, H, C, .), gamma (N, B, H, 1, D),
    each program instance's most negative cumulative log decay (B, N /
    at_once, H))."""
    batch, seq, features = q.shape
    width, n = features // heads, seq // chunk
    at_once, tile, instances = _cut(seq, chunk)
    band, per_head, leading, whole = _specs(heads, width, chunk, at_once)
    pattern = jnp.asarray(cumulative(chunk, tile), jnp.bfloat16)
    blocks = len(levels(chunk)) + 2
    shape = lambda *tile, dtype=q.dtype: jax.ShapeDtypeStruct((n, batch, heads, *tile), dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tile=tile, tiles=at_once * chunk // tile),
        grid=(batch, instances, heads),
        in_specs=[whole(pattern.shape), band, band, band, band, per_head],
        out_specs=[leading(chunk, width), leading(chunk, chunk), leading(chunk, width), leading(chunk, width),
                   leading(chunk, width), leading(1, width),
                   pl.BlockSpec((None, instances, heads), lambda b, n, h: (b, 0, 0))],
        out_shape=[shape(chunk, width), shape(chunk, chunk), shape(chunk, width), shape(chunk, width, dtype=jnp.float32),
                   shape(chunk, width), shape(1, width, dtype=jnp.float32),
                   jax.ShapeDtypeStruct((batch, instances, heads), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blocks * tile, width), jnp.float32)],
        compiler_params=_params(), interpret=interpret, name="kda_operands_fwd",
    )(pattern, q, k, v, g, beta)


def bwd_call(q, k, v, g, beta, cts, heads: int, chunk: int, interpret: bool = False):
    """The forward's operands and the cotangents of its six results, in the
    forward's shapes (gamma's (N, B, H, 1, D)) -> dq, dk, dv, dg, dbeta like q,
    k, v, g, beta."""
    batch, seq, features = q.shape
    width = features // heads
    at_once, tile, instances = _cut(seq, chunk)
    band, per_head, leading, whole = _specs(heads, width, chunk, at_once)
    pattern = cumulative(chunk, tile)
    blocks = len(levels(chunk)) + 2
    pattern, pattern_t = jnp.asarray(pattern, jnp.bfloat16), jnp.asarray(pattern.T, jnp.bfloat16)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tile=tile, tiles=at_once * chunk // tile),
        grid=(batch, instances, heads),
        in_specs=[whole(pattern.shape), whole(pattern_t.shape), band, band, band, band, per_head,
                  leading(chunk, width), leading(chunk, chunk), leading(chunk, width), leading(chunk, width),
                  leading(chunk, width), leading(1, width)],
        out_specs=[band, band, band, band, per_head],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((blocks * tile, width), jnp.float32)] * 2,
        compiler_params=_params(), interpret=interpret, name="kda_operands_bwd",
    )(pattern, pattern_t, q, k, v, g, beta, *cts)


# ---- the scan over the chunks (`lm_kda._fused_scan`): the state carried in VMEM, one kernel each way ----------------------
# A program instance walks `CHUNKS_AT_ONCE` chunks of `SCAN_HEADS` heads of one sequence (the forward in order, the backward
# in reverse, through its index maps), each head's state (K, V) a float32 scratch that outlives the grid step: zeroed at
# the sequence's first block, carried across the others. Per chunk each does what `lm_kda._state_scan_fwd` / `_bwd` do, in
# the same precisions; the heads' chains are independent, so their small matmuls interleave where one head's would wait on
# the MXU. The output (and its cotangent) is (B, S, H * V), a head a band of lanes, as `kda_attention` holds it.
SCAN_HEADS = 4

_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def scan_cut(seq: int, chunk: int, heads: int) -> tuple[int, int, int]:
    """(chunks a program instance walks, program instances a sequence, heads
    a program instance carries)."""
    at_once, _, instances = _cut(seq, chunk)
    return at_once, instances, max(h for h in range(1, min(SCAN_HEADS, heads) + 1) if heads % h == 0)


def _starts_a_sequence():
    """Whether this grid step is its sequence's first block (its carry starts at 0)."""
    return pl.program_id(2) == 0


def _by_row(gamma, values):
    """gamma (1, K) -> (K, V) whose row k is gamma_k: the state's rows decay by key channel."""
    return jnp.broadcast_to(gamma, (values, gamma.shape[1])).T


def _scan_fwd_kernel(qg_ref, b_ref, w_ref, u0_ref, kh_ref, gamma_ref, out_ref, states_ref, state_ref, *, chunk, at_once):
    @pl.when(_starts_a_sequence())
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    cd = out_ref.dtype
    values = state_ref.shape[2]

    def one(c, _):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        for h in range(state_ref.shape[0]):
            state = state_ref[h]
            s = state.astype(cd)
            states_ref[c, h] = s
            u = (u0_ref[c, h] - jnp.dot(w_ref[c, h], s, **_F32)).astype(cd)
            out = jnp.dot(qg_ref[c, h], s, **_F32) + jnp.dot(b_ref[c, h], u, **_F32)
            out_ref[rows, h * values:(h + 1) * values] = out.astype(cd)
            state_ref[h] = _by_row(gamma_ref[c, h], values) * state + lax.dot_general(kh_ref[c, h], u, _TN, **_F32)

    lax.fori_loop(0, at_once, one, None)


def _scan_bwd_kernel(qg_ref, b_ref, w_ref, u0_ref, kh_ref, gamma_ref, states_ref, ct_ref,
                     dqg_ref, db_ref, dw_ref, du0_ref, dkh_ref, dgamma_ref, d_state_ref, *, chunk, at_once):
    @pl.when(_starts_a_sequence())
    def _():
        d_state_ref[...] = jnp.zeros(d_state_ref.shape, jnp.float32)

    cd = states_ref.dtype
    values = d_state_ref.shape[2]

    def one(i, _):
        c = at_once - 1 - i  # the block's chunks from its last: the blocks come in reverse too
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        for h in range(d_state_ref.shape[0]):
            d_state = d_state_ref[h]  # the cotangent of the state at the chunk's END
            s, ds, do = states_ref[c, h], d_state.astype(cd), ct_ref[rows, h * values:(h + 1) * values].astype(cd)
            w = w_ref[c, h]
            u = (u0_ref[c, h] - jnp.dot(w, s, **_F32)).astype(cd)
            du = lax.dot_general(b_ref[c, h], do, _TN, **_F32) + jnp.dot(kh_ref[c, h], ds, **_F32)
            duc = du.astype(cd)
            d_state_ref[h] = (_by_row(gamma_ref[c, h], values) * d_state + lax.dot_general(qg_ref[c, h], do, _TN, **_F32)
                              - lax.dot_general(w, duc, _TN, **_F32))
            dqg_ref[c, h] = lax.dot_general(do, s, _NT, **_F32).astype(cd)
            db_ref[c, h] = lax.dot_general(do, u, _NT, **_F32).astype(cd)
            dw_ref[c, h] = (-lax.dot_general(duc, s, _NT, **_F32)).astype(cd)
            du0_ref[c, h] = du
            dkh_ref[c, h] = lax.dot_general(u, ds, _NT, **_F32).astype(cd)
            dgamma_ref[c, h] = jnp.sum((s.astype(jnp.float32) * d_state).T, axis=0, keepdims=True)

    lax.fori_loop(0, at_once, one, None)


def _scan_specs(seq, chunk, heads, reverse):
    """(at_once, instances, heads a program instance, a chunk-leading block of
    (N, B, H, ...), a (B, S, H * width) block of `width` lanes a head)."""
    at_once, instances, group = scan_cut(seq, chunk, heads)
    at = (lambda n: instances - 1 - n) if reverse else (lambda n: n)  # noqa: E731
    leading = lambda *tile: pl.BlockSpec((at_once, None, group, *tile), lambda b, h, n: (at(n), b, h, 0, 0))  # noqa: E731
    band = lambda width: pl.BlockSpec((None, at_once * chunk, group * width), lambda b, h, n: (b, at(n), h))  # noqa: E731
    return at_once, instances, group, leading, band


def _scan_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def scan_fwd_call(qg, b, w, u0, kh, gamma, interpret: bool = False):
    """`_state_scan`'s operands, chunk-leading (N, B, H, ...): qg, w, kh (.., C,
    K) and b (.., C, C) in the compute dtype, u0 (.., C, V) and gamma (.., 1, K)
    float32 -> (out (B, N * C, H * V), states (N, B, H, K, V): each chunk's
    start state), both in the compute dtype."""
    n, batch, heads, chunk, width = qg.shape
    values = u0.shape[-1]
    at_once, instances, group, leading, band = _scan_specs(n * chunk, chunk, heads, reverse=False)
    return pl.pallas_call(
        functools.partial(_scan_fwd_kernel, chunk=chunk, at_once=at_once),
        grid=(batch, heads // group, instances),
        in_specs=[leading(chunk, width), leading(chunk, chunk), leading(chunk, width), leading(chunk, values),
                  leading(chunk, width), leading(1, width)],
        out_specs=[band(values), leading(width, values)],
        out_shape=[jax.ShapeDtypeStruct((batch, n * chunk, heads * values), qg.dtype),
                   jax.ShapeDtypeStruct((n, batch, heads, width, values), qg.dtype)],
        scratch_shapes=[pltpu.VMEM((group, width, values), jnp.float32)],
        compiler_params=_scan_params(), interpret=interpret, name="kda_scan_fwd",
    )(qg, b, w, u0, kh, gamma)


def scan_bwd_call(qg, b, w, u0, kh, gamma, states, ct, interpret: bool = False):
    """The forward's operands, its states and the cotangent of its output (B,
    S, H * V) -> the cotangents of the six operands in their own shapes and
    dtypes (u0's and gamma's float32)."""
    n, batch, heads, chunk, width = qg.shape
    values = u0.shape[-1]
    at_once, instances, group, leading, band = _scan_specs(n * chunk, chunk, heads, reverse=True)
    operands = (qg, b, w, u0, kh, gamma)
    specs = [leading(chunk, width), leading(chunk, chunk), leading(chunk, width), leading(chunk, values),
             leading(chunk, width), leading(1, width)]
    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk, at_once=at_once),
        grid=(batch, heads // group, instances),
        in_specs=[*specs, leading(width, values), band(values)],
        out_specs=specs,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in operands],
        scratch_shapes=[pltpu.VMEM((group, width, values), jnp.float32)],
        compiler_params=_scan_params(), interpret=interpret, name="kda_scan_bwd",
    )(*operands, states, ct)


# ---- the short convolutions (`lm_kda.conv_and_norm`): causal conv, SiLU and a head's L2 norm, one pass each way ----------
# Memory-bound passes with little arithmetic, kept apart from the VPU-bound kernels above: one program instance is one tile,
# each head a band of its lanes; rows are shifted by sublane rotations (`pltpu.roll`) of the tile stacked under its halo.
# A tile is CONV_ROWS rows (positions) by CONV_LANES lanes (whole heads) of (B, S, H * D), the halo a neighbour's
# CONV_HALO rows (lm_kda's): one bfloat16 sublane tile, behind the tile (the filter's history) and, in the backward, ahead.
# A Mamba-2 site (ops/lm_mamba.py) hands in a bias as one more operand, added before the SiLU, and no norm; its pair is
# named after its scope (`ssd_conv_fwd` / `_bwd`). Without a bias a kernel has the operands it always had.
CONV_ROWS = 512
CONV_LANES = 512


def conv_cut(seq: int, features: int, width: int) -> tuple[int, int]:
    """(rows, lanes) of a tile: the most rows up to `CONV_ROWS` in whole halo
    tiles that divide the sequence, and the most whole heads of `width` lanes
    up to `CONV_LANES` that divide the features (one head where a head is wider)."""
    rows = max(r for r in range(CONV_HALO, min(CONV_ROWS, seq) + 1, CONV_HALO) if seq % r == 0)
    lanes = max([m * width for m in range(1, CONV_LANES // width + 1) if features % (m * width) == 0] or [width])
    return rows, lanes


def _behind(x, back, rows):
    """Rows [CONV_HALO - back, CONV_HALO - back + rows) of x (float32, the tile's
    history in its first CONV_HALO rows): x rolled down by `back`, an aligned slice."""
    return (pltpu.roll(x, back, 0) if back else x)[CONV_HALO:CONV_HALO + rows]


def _history(ref, band, first_row):
    """A halo block's band in float32 and each row's position (`first_row` the
    first's): the caller zeroes what lies outside the sequence. A halo index
    is clamped to the sequence, so those rows hold a real block's data."""
    x = ref[:, band].astype(jnp.float32)
    return x, first_row + lax.broadcasted_iota(jnp.int32, x.shape, 0)


def _conv_fwd_kernel(*refs, width, scale, eps, biased):
    w_ref, bias_ref, behind_ref, z_ref, out_ref = refs if biased else (refs[0], None, *refs[1:])
    rows, lanes = z_ref.shape
    taps = w_ref.shape[0]
    start = pl.program_id(1) * rows
    for h in range(lanes // width):
        band = slice(h * width, (h + 1) * width)
        history, at = _history(behind_ref, band, start - CONV_HALO)
        x = jnp.concatenate([jnp.where(at >= 0, history, 0.0), z_ref[:, band].astype(jnp.float32)], axis=0)
        pre = sum(w_ref[pl.ds(i, 1), band] * _behind(x, taps - 1 - i, rows) for i in range(taps))
        if bias_ref is not None:
            pre = pre + bias_ref[:, band]
        c = pre * jax.nn.sigmoid(pre)
        if scale is not None:
            c = c * (lax.rsqrt(jnp.sum(c * c, axis=1, keepdims=True) + eps) * scale)
        out_ref[:, band] = c.astype(out_ref.dtype)


def _conv_bwd_kernel(*refs, width, scale, eps, seq, biased):
    if biased:
        w_ref, bias_ref, behind_ref, z_ref, ahead_ref, dy_ref, dy_ahead_ref, dz_ref, dw_ref, db_ref = refs
    else:
        (w_ref, behind_ref, z_ref, ahead_ref, dy_ref, dy_ahead_ref, dz_ref, dw_ref), bias_ref, db_ref = refs, None, None
    rows, lanes = z_ref.shape
    taps = w_ref.shape[0]
    start = pl.program_id(1) * rows
    reach = rows + CONV_HALO  # the pre-activations remade: the tile's and the next CONV_HALO rows', whose taps reach back into it
    for h in range(lanes // width):
        band = slice(h * width, (h + 1) * width)
        history, at = _history(behind_ref, band, start - CONV_HALO)
        later, later_at = _history(ahead_ref, band, start + rows)
        x = jnp.concatenate([jnp.where(at >= 0, history, 0.0), z_ref[:, band].astype(jnp.float32),
                             jnp.where(later_at < seq, later, 0.0)], axis=0)
        shifted = [_behind(x, taps - 1 - i, reach) for i in range(taps)]  # z_{t - (taps - 1) + i}, t from the tile's first row
        pre = sum(w_ref[pl.ds(i, 1), band] * shifted[i] for i in range(taps))
        if bias_ref is not None:
            pre = pre + bias_ref[:, band]
        gate = jax.nn.sigmoid(pre)
        dy_later, dy_at = _history(dy_ahead_ref, band, start + rows)
        dc = jnp.concatenate([dy_ref[:, band].astype(jnp.float32), jnp.where(dy_at < seq, dy_later, 0.0)], axis=0)
        if scale is not None:  # y = s c r, r = (c . c + eps)^-1/2  =>  dc = s r dy - s r^3 c (dy . c)
            c = pre * gate
            r = lax.rsqrt(jnp.sum(c * c, axis=1, keepdims=True) + eps)
            dc = (scale * r) * (dc - c * (r * r * jnp.sum(dc * c, axis=1, keepdims=True)))
        d_pre = dc * (gate * (1.0 + pre * (1.0 - gate)))
        # z_t feeds the pre-activation at t + (taps - 1) - i through tap i: rolled UP by that many rows
        dz = sum(w_ref[pl.ds(i, 1), band] * (pltpu.roll(d_pre, reach - (taps - 1 - i), 0) if taps - 1 - i else d_pre)[:rows]
                 for i in range(taps))
        dz_ref[:, band] = dz.astype(dz_ref.dtype)
        for i in range(taps):
            dw_ref[pl.ds(i, 1), band] = jnp.sum(d_pre[:rows] * shifted[i][:rows], axis=0, keepdims=True)
        if db_ref is not None:
            db_ref[:, band] = jnp.sum(d_pre[:rows], axis=0, keepdims=True)


def _conv_specs(seq, features, width, taps):
    rows, lanes = conv_cut(seq, features, width)
    step, last = rows // CONV_HALO, seq // CONV_HALO - 1
    tile = pl.BlockSpec((None, rows, lanes), lambda b, i, j: (b, i, j))
    behind = pl.BlockSpec((None, CONV_HALO, lanes), lambda b, i, j: (b, jnp.maximum(i * step - 1, 0), j))
    ahead = pl.BlockSpec((None, CONV_HALO, lanes), lambda b, i, j: (b, jnp.minimum((i + 1) * step, last), j))
    taps_spec = pl.BlockSpec((taps, lanes), lambda b, i, j: (0, j))
    return rows, lanes, tile, behind, ahead, taps_spec


def _bias_spec(lanes):
    """A bias (1, H * D) float32: a tile's lanes of it."""
    return pl.BlockSpec((1, lanes), lambda b, i, j: (0, j))


def _conv_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel"), vmem_limit_bytes=VMEM_LIMIT_BYTES)


def conv_fwd_call(z, w, width: int, scale, eps: float, interpret: bool = False, bias=None, name: str = "kda_conv"):
    """z (B, S, H * D) in the compute dtype, w (taps, H * D), bias None or (H *
    D,) -> SiLU of the causal convolution (plus the bias), each head of `width`
    lanes L2-normalised and times `scale` unless `scale` is None; in z's dtype.
    The instruction is `<name>_fwd`. Without a bias the kernel and its operands
    are what they were before there was one."""
    batch, seq, features = z.shape
    rows, lanes, tile, behind, _, taps_spec = _conv_specs(seq, features, width, w.shape[0])
    biased = bias is not None
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, width=width, scale=scale, eps=eps, biased=biased),
        grid=(batch, seq // rows, features // lanes),
        in_specs=[taps_spec, *([_bias_spec(lanes)] if biased else []), behind, tile], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(z.shape, z.dtype),
        compiler_params=_conv_params(), interpret=interpret, name=name + "_fwd",
    )(w.astype(jnp.float32), *([bias.astype(jnp.float32).reshape(1, features)] if biased else []), z, z)


def conv_bwd_call(z, w, ct, width: int, scale, eps: float, interpret: bool = False, bias=None, name: str = "kda_conv"):
    """(dz in z's dtype, float32 dw of each tile (B, S / rows, taps, H * D)
    and, with a bias, float32 db of each tile (B, S / rows, 1, H * D)) from the
    forward's operands and the cotangent of its result."""
    batch, seq, features = z.shape
    taps = w.shape[0]
    rows, lanes, tile, behind, ahead, taps_spec = _conv_specs(seq, features, width, taps)
    biased = bias is not None
    per_tile = lambda n: pl.BlockSpec((None, None, n, lanes), lambda b, i, j: (b, i, 0, j))  # noqa: E731
    sums = lambda n: jax.ShapeDtypeStruct((batch, seq // rows, n, features), jnp.float32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, width=width, scale=scale, eps=eps, seq=seq, biased=biased),
        grid=(batch, seq // rows, features // lanes),
        in_specs=[taps_spec, *([_bias_spec(lanes)] if biased else []), behind, tile, ahead, tile, ahead],
        out_specs=[tile, per_tile(taps), *([per_tile(1)] if biased else [])],
        out_shape=[jax.ShapeDtypeStruct(z.shape, z.dtype), sums(taps), *([sums(1)] if biased else [])],
        compiler_params=_conv_params(), interpret=interpret, name=name + "_bwd",
    )(w.astype(jnp.float32), *([bias.astype(jnp.float32).reshape(1, features)] if biased else []), z, z, z, ct, ct)
